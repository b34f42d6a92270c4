"""Host-side tests of bench.py's measurement machinery (the benches
themselves need the real chip; the steadiness statistics and roofline
accounting they report must not).  VERDICT round-3 #4/#5."""

import json

import numpy as np
import pytest

import bench
from elasticdl_tpu.obs.stepstats import V5E


def test_median_spread_basics():
    median, spread = bench._median_spread([1.0, 2.0, 4.0], 8.0)
    # rates 8, 4, 2 -> median 4, spread (8-2)/4
    assert median == 4.0
    assert spread == pytest.approx(1.5)


def test_trimmed_median_spread_drops_one_outlier_each_side():
    # One contended run (10x slow) must not blow up the spread.
    times = [1.0, 1.02, 0.98, 1.01, 10.0, 0.99, 1.0]
    median, spread = bench._trimmed_median_spread(times, 100.0)
    assert 95 < median < 105
    assert spread < 0.1
    with pytest.raises(AssertionError):
        bench._trimmed_median_spread([1.0] * 4, 1.0)


def test_roofline_fields_every_tracked_metric():
    """Every SELF_BASELINE metric emits a roofline anchor, and the
    fractions are sane at the recorded baseline values."""
    for metric, value in bench.SELF_BASELINE.items():
        fields = bench._roofline_fields(metric, value, V5E)
        assert fields, f"no roofline fields for {metric}"
        fracs = [
            v for k, v in fields.items()
            if k in ("mfu", "bw_frac", "floor_frac", "host_parse_frac",
                     "device_frac")
        ]
        assert fracs, f"no fraction field for {metric}: {fields}"
        for frac in fracs:
            assert 0.0 < frac <= 1.2, (metric, fields)


def test_transformer_flops_model():
    # d512 L4 V32k mlp4 T2048 causal: lm_head 2dV = 33.6M/token; the
    # 4 layers add ~33.6M more (24d^2 + 4d*T/2 each).
    per_token = bench._transformer_flops_per_token()
    assert 60e6 < per_token < 75e6, per_token


def test_emit_json_contract(capsys):
    bench._emit(
        "transformer_lm_tokens_per_sec_per_chip", 242_000.0,
        "tokens/sec/chip", 0.01, device_kind=V5E, tracked=False,
    )
    row = json.loads(capsys.readouterr().out.strip())
    assert row["metric"] == "transformer_lm_tokens_per_sec_per_chip"
    assert row["unit"] == "tokens/sec/chip"
    assert row["tracked"] is False
    assert 0 < row["mfu"] < 1
    assert row["vs_baseline"] == pytest.approx(242_000.0 / 241_046.0, rel=1e-3)


def test_final_emit_carries_every_metric(capsys):
    """The driver's BENCH_r{N}.json preserves only the parsed FINAL line;
    final=True must fold every previously emitted row into `all` so the
    artifact alone reconstructs the round (VERDICT round-4 weak #1)."""
    bench._EMITTED.clear()
    bench._emit(
        "resnet50_images_per_sec_per_chip", 2_665.0, "images/sec/chip",
        0.01, device_kind=V5E,
    )
    bench._emit(
        "deepfm_26m_strict_samples_per_sec_per_chip", 272_953.0,
        "samples/sec/chip", 0.01, device_kind=V5E,
    )
    bench._emit(
        "deepfm_train_samples_per_sec_per_chip", 975_000.0,
        "samples/sec/chip", 0.001, final=True, device_kind=V5E,
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert "all" not in json.loads(lines[0])
    final = json.loads(lines[-1])
    assert set(final["all"]) == {
        "resnet50_images_per_sec_per_chip",
        "deepfm_26m_strict_samples_per_sec_per_chip",
        "deepfm_train_samples_per_sec_per_chip",
    }
    resnet = final["all"]["resnet50_images_per_sec_per_chip"]
    assert resnet["value"] == 2_665.0
    assert resnet["unit"] == "images/sec/chip"
    assert "vs_baseline" in resnet and "spread" in resnet
    strict = final["all"]["deepfm_26m_strict_samples_per_sec_per_chip"]
    assert strict["bound"] == "table-stream"
    # The headline row itself is in `all` too — one artifact, whole round.
    assert final["all"]["deepfm_train_samples_per_sec_per_chip"][
        "value"
    ] == final["value"]
    bench._EMITTED.clear()


def test_ring_roofline_reads_ring_bench_config():
    """_roofline_fields' ring FLOP accounting must follow RING_BENCH (the
    dict bench_ring_engine also reads) — a divergent copy would silently
    emit a wrong mfu (round-4 ADVICE)."""
    base = bench._roofline_fields(
        "ring_attention_tokens_per_sec_per_chip", 1_977_558.0, V5E
    )
    orig = dict(bench.RING_BENCH)
    try:
        bench.RING_BENCH["t_local"] = orig["t_local"] * 2
        doubled = bench._roofline_fields(
            "ring_attention_tokens_per_sec_per_chip", 1_977_558.0, V5E
        )
    finally:
        bench.RING_BENCH.clear()
        bench.RING_BENCH.update(orig)
    # FLOPs/group scale with t_local^2 but tokens/group only with
    # t_local -> achieved flops at fixed token rate doubles.
    assert doubled["mfu"] == pytest.approx(2 * base["mfu"], rel=0.02)


def test_backend_probe_prints_contract(capsys):
    """The fail-fast device probe (bench._require_tpu) must emit its
    explanatory line BEFORE touching the backend — that line is what
    makes an exit without metrics diagnosable from the recorded output
    tail.  The probe itself is injected: a host-side meta test must
    never initialize a backend."""
    bench._require_tpu(probe_fn=lambda: ("tpu", V5E, 1))
    out = capsys.readouterr().out
    assert "bench_backend_probe" in out.splitlines()[0]
    assert "backend live: 1" in out


@pytest.mark.parametrize(
    "probe,error",
    [
        # No accelerator: never finish on the CPU.
        (("cpu", "cpu", 8), SystemExit),
        # A TPU the peaks table does not list: an error, not a default.
        (("tpu", "TPU v9 imaginary", 1), RuntimeError),
    ],
)
def test_backend_probe_refuses_other_devices(capsys, probe, error):
    with pytest.raises(error) as exc:
        bench._require_tpu(probe_fn=lambda: probe)
    if error is SystemExit:
        assert exc.value.code != 0
    out = capsys.readouterr().out
    assert "backend live" not in out


def test_roofline_fields_refuse_an_unlisted_device():
    with pytest.raises(RuntimeError, match="no peaks recorded"):
        bench._roofline_fields(
            "transformer_lm_tokens_per_sec_per_chip", 242_000.0, "cpu"
        )


def test_ring_bench_harness_import():
    """bench_ring_engine loads scripts/exp_ring_perf.py by file path; pin
    the coupling (module loads, exposes run_variant, parses the exact
    variant string the bench builds) without touching a device."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "exp_ring_perf_for_test",
        os.path.join(
            os.path.dirname(__file__), os.pardir, "scripts",
            "exp_ring_perf.py",
        ),
    )
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    assert callable(harness.run_variant)
    cfg = harness.parse("t2048_b4_r4_pallas_i32")
    assert (cfg["t"], cfg["b"], cfg["r"], cfg["engine"], cfg["inner"]) == (
        2048, 4, 4, "pallas", 32,
    )
