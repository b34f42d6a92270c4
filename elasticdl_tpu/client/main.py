"""The `elasticdl` command-line client.

Parity: elasticdl_client/main.py in the reference — subcommand tree
`train | evaluate | predict | zoo init|build|push`.  Local mode runs the
master in-process; cluster modes render a master pod spec (phase 6).
"""

from __future__ import annotations

import sys

import elasticdl_tpu


def _print_usage():
    print(
        "elasticdl_tpu v{version}\n"
        "Usage: elasticdl <command> [flags]\n"
        "Commands:\n"
        "  train      Submit/run a training job\n"
        "  evaluate   Submit/run an evaluation job\n"
        "  predict    Submit/run a prediction job\n"
        "  zoo        Manage model zoo (init/build/push)\n".format(
            version=elasticdl_tpu.__version__
        )
    )


def main(argv=None):
    from elasticdl_tpu.obs import tracing

    # The end of `proc.start`; `elasticdl train` runs the master in this
    # process, and its boot starts here (closed where it serves).
    tracing.begin_boot("master.boot")
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        _print_usage()
        return 0
    command, rest = argv[0], argv[1:]
    if command in ("train", "evaluate", "predict"):
        with tracing.early_span("master.imports"):
            from elasticdl_tpu.client import api

        return getattr(api, command)(rest)
    if command == "zoo":
        from elasticdl_tpu.client import zoo

        return zoo.main(rest)
    print(f"Unknown command: {command!r}", file=sys.stderr)
    _print_usage()
    return 2


if __name__ == "__main__":
    sys.exit(main())
