"""The trace reduction, on the tail of a trace recorded on the chip
(`gpt2-medium.train-synth`, seed 2147483661, my chip run, PR 23: from just
before the third training program to the end of the trace, where the
profiler's stop cut the fourth) and on made-up traces.
"""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import xplane  # noqa: E402


def trace(ops, programs=(), host=(), chip=0):
    lines = [{"name": "XLA Ops", "events": [list(e) for e in ops]}]
    if programs:
        lines.append({"name": "XLA Modules",
                      "events": [list(e) for e in programs]})
    planes = [{"name": f"/device:TPU:{chip}", "lines": lines}]
    if host:
        planes.append({"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [list(e) for e in host]}]})
    return {"planes": planes}


def test_recorded_tail_keeps_the_whole_program_and_drops_the_cut_one():
    path = os.path.join(HERE, "data", "gpt2_trace_tail.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    got = xplane.reduce(recorded)
    assert got["chips"] == 1 and got["programs"] == 1
    # One two-step program of GPT-2 medium: 512.6 ms, the chip busy all of it.
    assert got["window_s"] == pytest.approx(0.51256983, rel=1e-6)
    assert got["busy_s"] == pytest.approx(0.51254163, rel=1e-6)
    assert 0 <= got["idle_share"] < 1e-3
    assert got["device_ops"][0][0] == "attn"
    assert got["device_ops"][0][1] == pytest.approx(0.105712982, rel=1e-6)
    assert len(got["device_ops"]) == 10
    assert sum(s for _, s in got["device_ops"]) <= got["busy_s"] * 1.05
    assert got["exposed_collective_s"] == 0.0


def test_busy_is_the_union_of_overlapping_ops():
    got = xplane.reduce(trace(
        [("%fusion.1 = f32[8] fusion(..)", 0, 100_000),
         ("%copy.2", 50_000, 100_000), ("%fusion.3", 300_000, 100_000)]
    ))
    assert got["busy_s"] == pytest.approx(250e-6)
    assert got["window_s"] == pytest.approx(400e-6)
    assert got["idle_share"] == pytest.approx(0.375)
    assert got["device_ops"] == [["fusion", 200e-6], ["copy", 100e-6]]
    assert got["programs"] == 0


def test_container_ops_are_not_ranked_beside_their_bodies():
    got = xplane.reduce(trace(
        [("%while.5", 0, 100_000), ("%fusion.1", 10_000, 80_000)]
    ))
    assert [name for name, _ in got["device_ops"]] == ["fusion"]
    assert got["busy_s"] == pytest.approx(100e-6)


def test_a_program_cut_by_the_end_of_the_trace_is_left_out():
    ops = [("%a.1", 0, 400_000), ("%a.1", 1_000_000, 400_000),
           ("%a.1", 2_000_000, 100_000)]
    programs = [("jit_step(1)", 0, 500_000), ("jit_tiny(2)", 600_000, 500),
                ("jit_step(1)", 1_000_000, 500_000),
                ("jit_step(1)", 2_000_000, 100_000)]
    got = xplane.reduce(trace(ops, programs))
    assert got["programs"] == 2
    assert got["window_s"] == pytest.approx(1.5e-3)
    assert got["busy_s"] == pytest.approx(0.8e-3)


def test_idle_is_charged_to_the_host_frames_own_time():
    ops = [("%a.1", 0, 100_000), ("%a.2", 500_000, 100_000)]
    host = [("$w/loop.py:10 outer", 0, 600_000),
            ("$w/data.py:20 read", 150_000, 250_000),
            ("$w/data.py:30 crc", 200_000, 100_000)]
    gaps = dict(xplane.reduce(trace(ops, host=host))["idle_gaps"])
    # The gap is [100, 500) us: crc has 100 of it, read 250 - 100, outer the
    # rest (50 before read, 100 after).
    assert gaps["data.py:30_crc"] == pytest.approx(100e-6)
    assert gaps["data.py:20_read"] == pytest.approx(150e-6)
    assert gaps["loop.py:10_outer"] == pytest.approx(150e-6)
    assert sum(gaps.values()) == pytest.approx(400e-6)


def test_idle_with_no_frame_over_it_is_said_so():
    ops = [("%a.1", 0, 100_000), ("%a.2", 500_000, 100_000)]
    gaps = dict(xplane.reduce(trace(ops))["idle_gaps"])
    assert gaps == {"no_host_frame": pytest.approx(400e-6)}


def test_collective_time_counts_only_where_no_compute_hides_it():
    one = trace([("%fusion.1", 0, 100_000),
                 ("%all-reduce.2", 50_000, 150_000)])
    two = trace([("%fusion.1", 0, 200_000)], chip=1)
    both = {"planes": one["planes"] + two["planes"]}
    got = xplane.reduce(both)
    assert got["chips"] == 2
    # chip 0: 100 us of the all-reduce are exposed; chip 1: none.
    assert got["exposed_collective_s"] == pytest.approx(50e-6)
    assert got["busy_s"] == pytest.approx(200e-6)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


@pytest.mark.parametrize("name, stem", [
    ("%fusion.101 = f32[3250000,128]{1,0} fusion(s32[1] %x)", "fusion"),
    ("copy.4", "copy"), ("%all-reduce.17", "all-reduce"), ("attn", "attn"),
    ("%multiply_add_fusion.2.1", "multiply_add_fusion"),
])
def test_op_stem(name, stem):
    assert xplane.op_stem(name) == stem


def test_dump_reads_a_trace_the_profiler_wrote(tmp_path):
    """`dump` on a trace made here: the CPU backend has no device plane, so
    only the host plane with its Python frames comes out."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path / "profile")):
        for _ in range(3):
            jax.jit(lambda x: (x @ x).sum())(jnp.ones((256, 256))).block_until_ready()
    out = tmp_path / "trace.json"
    xplane.dump(str(tmp_path / "profile"), str(out))
    planes = {p["name"]: p for p in json.loads(out.read_text())["planes"]}
    assert xplane.HOST_PLANE in planes
    frames = [
        e[0] for line in planes[xplane.HOST_PLANE]["lines"]
        for e in line["events"] if e[0].startswith("$")
    ]
    assert frames, "the Python tracer's frames are in the host plane"
