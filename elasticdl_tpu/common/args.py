"""Centralized flag system.

Parity: elasticdl/python/common/args.py in the reference — flat argparse with
distinct parser assemblies per role (master / worker / CLI) sharing flag
groups; unknown flags round-trip client -> master -> worker.
"""

from __future__ import annotations

import argparse


def pos_int(value):
    ivalue = int(value)
    if ivalue <= 0:
        raise argparse.ArgumentTypeError(f"{value} must be a positive integer")
    return ivalue


def non_neg_int(value):
    ivalue = int(value)
    if ivalue < 0:
        raise argparse.ArgumentTypeError(f"{value} must be >= 0")
    return ivalue


def pos_int_or_auto(value):
    if value == "auto":
        return value
    return pos_int(value)


def _profile_steps_spec(value):
    """Validate --profile_steps AT PARSE TIME (master-side): a malformed
    spec must fail the submission, not crash-loop every worker pod until
    the restart budget dies."""
    if value:
        from elasticdl_tpu.common.profiler import parse_profile_steps

        try:
            parse_profile_steps(value)
        except ValueError as e:
            raise argparse.ArgumentTypeError(str(e))
    return value


def str2bool(value):
    if isinstance(value, bool):
        return value
    if value.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if value.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError(f"Cannot parse bool from {value!r}")


def add_common_arguments(parser: argparse.ArgumentParser):
    parser.add_argument("--job_name", default="elasticdl-job", help="Job name")
    parser.add_argument(
        "--distribution_strategy",
        default="Local",
        choices=["Local", "ParameterServerStrategy", "AllreduceStrategy"],
        help="Local, ParameterServerStrategy (sharded-embedding data plane) "
        "or AllreduceStrategy (psum over ICI)",
    )
    parser.add_argument("--log_level", default="INFO")


def add_model_zoo_arguments(parser: argparse.ArgumentParser):
    parser.add_argument(
        "--model_zoo", required=True, help="Directory or module path of the model zoo"
    )
    parser.add_argument(
        "--model_def",
        required=True,
        help="Model module within the zoo, e.g. mnist.mnist_functional_api",
    )
    parser.add_argument(
        "--model_params",
        default="",
        help="Comma-separated key=value pairs passed to custom_model()",
    )
    parser.add_argument("--dataset_fn", default="dataset_fn")
    parser.add_argument("--loss", default="loss")
    parser.add_argument("--optimizer", default="optimizer")
    parser.add_argument("--eval_metrics_fn", default="eval_metrics_fn")
    parser.add_argument("--custom_data_reader", default="custom_data_reader")
    parser.add_argument("--callbacks", default="callbacks")


def add_data_arguments(parser: argparse.ArgumentParser):
    parser.add_argument("--training_data", default="", help="Training data path/pattern")
    parser.add_argument("--validation_data", default="", help="Validation data path")
    parser.add_argument("--prediction_data", default="", help="Prediction data path")
    parser.add_argument("--records_per_task", type=pos_int, default=4096)
    parser.add_argument("--minibatch_size", type=pos_int, default=64)
    parser.add_argument("--num_epochs", type=pos_int, default=1)
    parser.add_argument(
        "--data_reader_params",
        default="",
        help="Comma-separated key=value pairs passed to the data reader",
    )


def add_train_arguments(parser: argparse.ArgumentParser):
    parser.add_argument("--evaluation_steps", type=non_neg_int, default=0,
                        help="Evaluate every N steps (0: per epoch)")
    parser.add_argument("--checkpoint_steps", type=non_neg_int, default=0)
    parser.add_argument("--checkpoint_dir", default="")
    parser.add_argument("--keep_checkpoint_max", type=non_neg_int, default=3)
    parser.add_argument("--output", default="", help="Trained model output path")
    parser.add_argument("--tensorboard_log_dir", default="")
    parser.add_argument(
        "--dense_sharding", default="replicated",
        choices=["replicated", "fsdp"],
        help="Dense param/optimizer placement in AllReduce mode: "
        "'replicated' (psum gradients) or 'fsdp' (state sharded over the "
        "data axis — each chip holds 1/N of model+optimizer memory; XLA "
        "inserts the weight all-gathers / gradient reduce-scatters)",
    )
    parser.add_argument(
        "--train_window_steps", type=non_neg_int, default=0,
        help="Training batches fused per device dispatch in cluster "
        "strategies. 0 = AUTO: up to 400 steps (the measured optimum, "
        "BASELINE.md dispatch-window scaling), bounded by the task's "
        "batch count and a 1 GiB staged-bytes cap. Explicit values "
        "override the auto sizing entirely.",
    )
    parser.add_argument(
        "--sparse_apply_every", type=pos_int_or_auto, default="auto",
        help="ParameterServerStrategy only: apply the sparse embedding "
        "optimizer once per N train steps from the accumulated gradients "
        "(N=1 is strict per-step semantics). N>1 trades bounded "
        "staleness — forwards within a chunk read chunk-start tables, "
        "the async-PS behaviour of upstream ElasticDL — for amortizing "
        "the table-sized moment update, the dominant step cost once the "
        "per-chip table exceeds ~10M rows (BASELINE.md table-scale "
        "probe). The default 'auto' resolves from the model's resident "
        "table rows at init: strict (1) up to 10M rows, 32 above — the "
        "convergence-validated large-table config (BASELINE.md "
        "'Windowed-apply convergence'; upstream ElasticDL's async PS was "
        "likewise its default mode). Pass 1 to force strict semantics at "
        "any scale. Chunks never span device dispatches: the worker "
        "grows --train_window_steps to a multiple of N, and task-tail "
        "batches outside a full window apply per-step.",
    )
    parser.add_argument(
        "--sparse_kernel", default="auto", choices=["xla", "fused", "auto"],
        help="ParameterServerStrategy sparse-path engine: 'xla' (packed "
        "gather + one-hot select lookups, stream/scatter optimizer "
        "apply) or 'fused' (the Pallas kernels in ops/sparse_embedding "
        "— lookup, dedup+apply, and the DeepFM FM interaction keep "
        "touched rows in VMEM instead of round-tripping [n, 128] HBM "
        "intermediates; single-device tables only in v1, bit-exactness "
        "contract in docs/design.md). 'auto' currently resolves to xla "
        "— the fused kernels' chip numbers are queued driver work "
        "(BASELINE.md) and auto never moves the headline onto "
        "unmeasured code.",
    )
    parser.add_argument(
        "--pipeline", default="sync", choices=["sync", "async"],
        help="Step-execution pipeline (data/pipeline.py). 'sync' is the "
        "classic serial loop (parse -> stage -> dispatch, reference "
        "parity). 'async' overlaps the host with the device: bounded "
        "background prefetch runs parse/batching off the step loop's "
        "critical path, staging for window N+1 issues while window N "
        "executes (booked as overlap_s in step anatomy, not data_wait/"
        "stage), and a parse pool (--parse_pool_workers) fans chunk "
        "parsing across host cores. Training results are bit-identical "
        "to sync (tests/test_pipeline.py proves it); pipelines drain "
        "at every task/rendezvous boundary so elastic events never see "
        "a stale in-flight batch.",
    )
    parser.add_argument(
        "--parse_pool_workers", type=non_neg_int, default=0,
        help="Host parse-pool threads for --pipeline async (0 = parse "
        "on the prefetch thread). numpy releases the GIL for the "
        "columnar parse, so threads scale with physical cores; size to "
        "~cores-2, leaving the step loop and heartbeat their own.",
    )
    parser.add_argument(
        "--pipeline_inflight", type=pos_int, default=2,
        help="--pipeline async read-ahead bound: max batches buffered "
        "between the prefetch producer and the step loop. The "
        "backpressure contract — a slow device stalls the producer at "
        "this bound instead of growing host memory.",
    )
    parser.add_argument(
        "--dispatch_depth", type=pos_int, default=2,
        help="--pipeline async: how many dispatched windows are assumed "
        "in flight on the device queue for overlap accounting (staging "
        "issued with a dispatch outstanding books as overlap_s).",
    )
    parser.add_argument(
        "--oov_diagnostics", type=str2bool, nargs="?", const=True,
        default=False,
        help="Report per-step counts of embedding ids >= vocab_size in "
        "worker logs instead of dropping them silently. The fixed-vocab "
        "contract (docs/design.md): out-of-range ids read zeros and "
        "receive no update — upstream ElasticDL's PS lazily grew such "
        "rows; port open-vocabulary models by hashing ids into fixed "
        "bins (preprocessing.Hashing).",
    )
    parser.add_argument(
        "--profile_steps", default="", type=_profile_steps_spec,
        help="'START,END': each worker captures a jax.profiler trace of "
        "its training steps in [START, END) under "
        "<tensorboard_log_dir>/profile (TensorBoard Profile plugin)",
    )
    parser.add_argument(
        "--mesh_model_axis", type=pos_int, default=1,
        help="Size of the mesh's `model` axis in cluster strategies "
        "(total devices = data x model). >1 shards embedding tables over "
        "it (PS mode) and gives mesh-aware zoo models (custom_model() "
        "accepting `mesh`, e.g. transformer.transformer_lm) a parallel "
        "axis: ring-attention context parallelism by default, or "
        "Megatron-style tensor parallelism with "
        "--model_params model_axis_mode=tp",
    )
    parser.add_argument(
        "--task_timeout_s", type=non_neg_int, default=900,
        help="Requeue a dispatched task not reported done within this "
        "many seconds (0 disables). Nonzero by default as the liveness "
        "backstop for a LOST dispatch: get_task retries on "
        "DEADLINE_EXCEEDED, so a reply that died on the wire leaves the "
        "popped task in `doing` with no worker-crash to recover it — "
        "without a timeout the job would hang at job-end waiting on it "
        "forever. At-least-once semantics make a spurious requeue of a "
        "genuinely-slow task safe (it just re-runs).",
    )
    parser.add_argument(
        "--jax_compilation_cache_dir", default="",
        help="Persistent XLA compilation cache directory (shared across "
        "worker restarts); used only when JAX_COMPILATION_CACHE_DIR is "
        "unset, and defaults to <repo>/.jax_cache (common/"
        "compile_cache.py). Elastic recovery restarts the world with fresh "
        "processes; with the cache, the re-formed world's compiles are "
        "disk hits instead of recompiles — the dominant recovery cost "
        "after process start (BASELINE.md elasticity numbers).",
    )
    parser.add_argument(
        "--use_bf16", type=str2bool, nargs="?", const=True, default=True,
        help="Compute in bfloat16 on the MXU: forwarded to zoo models "
        "whose custom_model() accepts a use_bf16 parameter (explicit "
        "--model_params use_bf16=... wins)",
    )


def add_cluster_arguments(parser: argparse.ArgumentParser):
    parser.add_argument("--num_workers", type=pos_int, default=1)
    parser.add_argument("--master_addr", default="", help="host:port of the master")
    parser.add_argument("--master_port", type=non_neg_int, default=0,
                        help="0 picks a free port")
    parser.add_argument("--worker_pod_priority", default="")
    parser.add_argument(
        "--metrics_port", type=non_neg_int, default=None,
        help="Embed the observability exporter in the master on this "
        "port, serving /metrics (Prometheus text exposition), /healthz, "
        "and /debug/vars (JSON metric dump + event-journal tail). "
        "0 picks a free port (logged); omit to disable.",
    )
    parser.add_argument("--max_worker_restarts", type=non_neg_int, default=3)
    parser.add_argument("--namespace", default="default")
    parser.add_argument("--image_name", default="")
    parser.add_argument(
        "--need_elasticity", type=str2bool, nargs="?", const=True, default=True
    )
    parser.add_argument(
        "--policy_enabled", type=str2bool, nargs="?", const=True,
        default=True,
        help="Run the goodput-driven elastic policy engine "
        "(master/policy.py): scale-up gated on amortizing the measured "
        "rescale cost, scale-down/hold under rescale thrash, and "
        "budgeted straggler eviction. False = observe-only (PR-4/5 "
        "advisory behavior).",
    )
    parser.add_argument(
        "--policy_amortize_horizon_s", type=float, default=600.0,
        help="Scale-up is approved only when the marginal-throughput "
        "gain of the granted workers repays the goodput ledger's "
        "measured per-rescale cost within this many seconds (see "
        "docs/failure_model.md 'Policy enforcement' for tuning).",
    )
    parser.add_argument(
        "--policy_tick_interval_s", type=float, default=2.0,
        help="Seconds between policy-engine evaluation ticks.",
    )
    parser.add_argument(
        "--policy_min_workers", type=pos_int, default=1,
        help="Enforcement floor: no policy decision (eviction or "
        "scale-down) may shrink the fleet below this.",
    )
    parser.add_argument(
        "--policy_evict_after", type=pos_int, default=3,
        help="A straggler must stay flagged for this many CONSECUTIVE "
        "policy ticks before eviction (on top of the detector's own "
        "hysteresis — one noisy snapshot can never kill a worker).",
    )
    parser.add_argument(
        "--policy_kill_budget", type=non_neg_int, default=1,
        help="Straggler evictions allowed per budget window; 0 keeps "
        "the straggler path advisory-only.",
    )
    parser.add_argument(
        "--policy_kill_budget_window_s", type=float, default=600.0,
        help="Length of the straggler kill-budget window; the budget "
        "refills when a window elapses.",
    )
    parser.add_argument(
        "--slo_enabled", type=str2bool, nargs="?", const=True, default=True,
        help="Run the master's SLO plane (obs/slo.py): a metrics-history "
        "sampler + burn-rate evaluator feeding /slo and the policy "
        "engine's advisory input.",
    )
    parser.add_argument(
        "--slo_goodput_target", type=float, default=0.0,
        help="Goodput-ratio floor for the master goodput SLO; 0 "
        "registers no goodput SLO (the history sampler still runs for "
        "/slo sparklines).",
    )
    parser.add_argument(
        "--slo_compliance_window_s", type=float, default=3600.0,
        help="Rolling error-budget compliance window; the burn-rate "
        "alert windows are the canonical 30-day fractions of this "
        "(docs/observability.md 'SLO plane').",
    )
    parser.add_argument(
        "--slo_tick_interval_s", type=float, default=2.0,
        help="Seconds between SLO-plane sample+evaluate ticks.",
    )
    parser.add_argument(
        "--quality_drift_bins", type=non_neg_int, default=0,
        help="Hash buckets of the train-side feature-id sketch "
        "(obs/quality.py): each worker sketches every train batch into "
        "a process-local DriftMonitor for train-serve skew comparison; "
        "0 disables the hook (the default — no per-step cost).",
    )
    parser.add_argument(
        "--quality_drift_threshold", type=float, default=0.25,
        help="Train-serve sketch divergence (total variation) that "
        "journals a quality_drift breach edge.",
    )
    parser.add_argument(
        "--worker_liveness_timeout_s", type=non_neg_int, default=60,
        help="Kill+relaunch a worker whose heartbeat is silent this long "
        "(0 disables hung-worker detection)",
    )
    parser.add_argument(
        "--devices_per_worker", type=pos_int, default=1,
        help="TPU chips visible to each worker host (mesh = workers x devices)",
    )
    parser.add_argument(
        "--master_resource_request", default="",
        help='k8s resources for the master pod, e.g. "cpu=1,memory=2Gi"',
    )
    parser.add_argument(
        "--worker_resource_request", default="",
        help='k8s resources per worker pod, e.g. "cpu=4,memory=8Gi,google.com/tpu=1"',
    )
    parser.add_argument(
        "--tpu_slice", default="",
        help="Schedule workers onto a named TPU pod slice (e.g. "
        "'v5e-16'): each worker pod is one TPU VM host — it requests "
        "the host's chips (google.com/tpu) and pins to nodes of the "
        "slice's accelerator/topology labels; --num_workers must equal "
        "the slice's host count (v5e-16 = 4 hosts). See "
        "master/tpu_slice.py for known shapes.",
    )
    parser.add_argument(
        "--volume", default="",
        help="k8s volumes mounted into every job pod, e.g. "
        '"claim_name=ckpt-pvc,mount_path=/ckpt" or '
        '"host_path=/mnt/nfs,mount_path=/data"; separate multiple with ";". '
        "Elastic training needs --checkpoint_dir on such a shared mount.",
    )


def build_master_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="elasticdl_tpu master", allow_abbrev=False)
    add_common_arguments(parser)
    add_model_zoo_arguments(parser)
    add_data_arguments(parser)
    add_train_arguments(parser)
    add_cluster_arguments(parser)
    parser.add_argument("--job_type", default="training_with_evaluation")
    return parser


def build_worker_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="elasticdl_tpu worker", allow_abbrev=False)
    add_common_arguments(parser)
    add_model_zoo_arguments(parser)
    add_data_arguments(parser)
    add_train_arguments(parser)
    parser.add_argument("--worker_id", type=non_neg_int, required=True)
    parser.add_argument("--master_addr", required=True)
    parser.add_argument("--job_type", default="training_with_evaluation")
    return parser


def _validate_cross_flags(args):
    if getattr(args, "profile_steps", "") and not getattr(
        args, "tensorboard_log_dir", ""
    ):
        raise ValueError(
            "--profile_steps requires --tensorboard_log_dir (traces are "
            "written under it for the TensorBoard Profile plugin)"
        )


def parse_master_args(argv=None):
    args, unknown = build_master_parser().parse_known_args(argv)
    _apply_log_level(args)
    _validate_cross_flags(args)
    return args


def parse_worker_args(argv=None):
    args, unknown = build_worker_parser().parse_known_args(argv)
    _apply_log_level(args)
    _validate_cross_flags(args)
    return args


def _apply_log_level(args):
    from elasticdl_tpu.common.log_utils import set_default_level

    set_default_level(args.log_level)


def parse_dict_params(params: str) -> dict:
    """Parse 'a=1,b=hello,c=0.5' into {'a': 1, 'b': 'hello', 'c': 0.5}."""
    result = {}
    if not params:
        return result
    for item in params.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" not in item:
            raise ValueError(f"Malformed key=value pair: {item!r}")
        key, value = item.split("=", 1)
        for cast in (int, float):
            try:
                value = cast(value)
                break
            except ValueError:
                continue
        else:
            if isinstance(value, str):
                low = value.lower()
                if low in ("true", "false"):
                    value = low == "true"
        result[key.strip()] = value
    return result


def format_dict_params(params: dict) -> str:
    """Inverse of parse_dict_params: {'a': 1, 'b': True} -> 'a=1,b=true'.
    Used to record the RESOLVED model params (job flags injected by
    model_utils._forward_flag included) into serving artifacts, so a
    reload rebuilds the exact trained model — e.g. DeepFM's table layout
    follows sparse_apply_every, and an artifact recording only the raw
    --model_params string would rebuild the wrong structure."""
    def fmt(value):
        if isinstance(value, bool):
            return "true" if value else "false"
        return str(value)

    for key, value in params.items():
        # ',' is the only non-round-trippable character: parse splits
        # items on ',' before the first '=', so '=' inside a value (a
        # URL, a nested spec) survives the round trip intact.
        if isinstance(value, str) and "," in value:
            raise ValueError(
                f"model param {key}={value!r} cannot round-trip "
                "through the k=v,k=v format"
            )
    return ",".join(f"{k}={fmt(v)}" for k, v in sorted(params.items()))


def args_to_argv(args: argparse.Namespace, keys=None) -> list:
    """Round-trip a namespace back into --flag value argv (client -> pods)."""
    argv = []
    for key, value in sorted(vars(args).items()):
        if keys is not None and key not in keys:
            continue
        if value is None or value == "":
            continue
        argv.extend([f"--{key}", str(value)])
    return argv
