"""`elasticdl zoo` subcommand tests (reference: elasticdl_client
image_builder).  Everything short of invoking the docker daemon is real:
init scaffolds a loadable zoo module; build renders a self-contained
docker context (framework + zoo + Dockerfile)."""

import os

from elasticdl_tpu.client import zoo


def test_init_scaffolds_loadable_module(tmp_path):
    path = str(tmp_path / "myzoo")
    assert zoo.main(["init", path]) == 0
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.model_utils import load_model_spec

    spec = load_model_spec(
        parse_master_args(
            ["--model_zoo", path, "--model_def", "my_model",
             "--training_data", "t"]
        )
    )
    model = spec.build_model()
    import jax
    import numpy as np

    variables = model.init(jax.random.PRNGKey(0), np.zeros((2, 4), np.float32))
    out = model.apply(variables, np.zeros((2, 4), np.float32))
    assert out.shape == (2, 2)


def test_build_renders_self_contained_context(tmp_path):
    zoo_dir = str(tmp_path / "myzoo")
    zoo.main(["init", zoo_dir])
    context = str(tmp_path / "ctx")
    rc = zoo.main(
        ["build", zoo_dir, "--context", context, "--dockerfile-only",
         "--base-image", "my-jax-base:latest"]
    )
    assert rc == 0
    dockerfile = open(os.path.join(context, "Dockerfile")).read()
    assert "FROM my-jax-base:latest" in dockerfile
    assert "COPY elasticdl_tpu/" in dockerfile
    assert "COPY myzoo/" in dockerfile
    # Context is self-contained: framework package + zoo + no caches.
    assert os.path.exists(
        os.path.join(context, "elasticdl_tpu", "master", "pod_manager.py")
    )
    assert os.path.exists(os.path.join(context, "myzoo", "my_model.py"))
    assert not any(
        "__pycache__" in root for root, _, _ in os.walk(context)
    )


def test_build_missing_zoo_errors(tmp_path, capsys):
    rc = zoo.main(
        ["build", str(tmp_path / "nope"), "--context",
         str(tmp_path / "ctx"), "--dockerfile-only"]
    )
    assert rc == 1
    assert "not found" in capsys.readouterr().err


def test_build_refuses_context_overwriting_source(tmp_path, capsys):
    """`--context` pointing at the source's parent must never rmtree the
    user's real code."""
    zoo_dir = str(tmp_path / "myzoo")
    zoo.main(["init", zoo_dir])
    rc = zoo.main(
        ["build", zoo_dir, "--context", str(tmp_path), "--dockerfile-only"]
    )
    assert rc == 1
    assert "overwrite or nest" in capsys.readouterr().err
    assert os.path.exists(os.path.join(zoo_dir, "my_model.py"))  # intact
    # Nested-inside-source case: context under the zoo dir itself.
    rc = zoo.main(
        ["build", zoo_dir, "--context", os.path.join(zoo_dir, "ctx"),
         "--dockerfile-only"]
    )
    assert rc == 1
    assert os.path.exists(os.path.join(zoo_dir, "my_model.py"))


def test_a_model_directory_imports_no_other_models(tmp_path):
    """A stack under `model_zoo/<a>/` imports from `elasticdl_tpu`, from
    the zoo's top level (`model_zoo.lm_common`, `model_zoo.datasets`) and
    from its own directory, never from `model_zoo/<b>/`: what two models
    share has ONE owner, so an edit to a stack meets that model's cells
    alone.  And importing an 8k stack brings neither the GPT stack nor its
    ring attention with it."""
    import ast
    import glob
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    directories = {
        os.path.basename(os.path.dirname(path))
        for path in glob.glob(os.path.join(root, "model_zoo", "*", "*.py"))
    }
    assert {"transformer", "qwen3_next", "laguna"} <= directories
    crossing = []
    for path in sorted(glob.glob(os.path.join(root, "model_zoo", "*", "*.py"))):
        own = os.path.basename(os.path.dirname(path))
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and not node.level:
                names = [node.module or ""] + [
                    f"{node.module}.{alias.name}" for alias in node.names
                ]
            elif isinstance(node, ast.Import):
                names = [alias.name for alias in node.names]
            else:
                continue
            for name in names:
                # `model_zoo.<b>...`, or `<b>...` with the zoo on the path
                parts = name.split(".")
                if parts[0] == "model_zoo":
                    parts = parts[1:]
                if parts and parts[0] in directories - {own}:
                    crossing.append(
                        f"{os.path.relpath(path, root)}:{node.lineno} {name}"
                    )
    assert not crossing, crossing
    stacks = [
        "qwen3_next.qwen3_next_lm", "nemotron_h.nemotron_h_lm",
        "deepseek_v2.deepseek_v2_lm", "laguna.laguna_lm",
        "granite_hybrid.granite_hybrid_lm",
    ]
    code = (
        "import importlib, sys\n"
        "for stack in sys.argv[1:]:\n"
        "    importlib.import_module('model_zoo.' + stack)\n"
        "brought = [m for m in ('model_zoo.transformer.transformer_lm',\n"
        "    'elasticdl_tpu.parallel.ring_attention') if m in sys.modules]\n"
        "assert not brought, brought\n"
        "print('alone')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code] + stacks, cwd=root, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "alone" in proc.stdout
