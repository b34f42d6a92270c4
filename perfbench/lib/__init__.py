"""The yardstick's shared pieces."""

import importlib.util
import os


def load_module(path: str):
    """A Python file found by its path (a reader, a scenario, a reference:
    files that a cell names in data)."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + os.path.basename(path)[:-3].replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
