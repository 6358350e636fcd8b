"""The reduction from a profiler trace to per-layer numbers."""

import os

import pytest

import chipbench_smoke as cs
from chipbench import trace_reduce as tr

FIXTURE = os.path.join(cs.HERE, "data", "tpu_small.xplane.pb")


def synthetic():
    host = {"python": [("bench/window", 0, 1000), ("bench/step", 0, 600),
                       ("bench/draw", 600, 300), ("other", 0, 1000)]}
    dev = {
        "XLA Modules": [("jit_train_step(11)", 100, 300),
                        ("jit_sample_gather(22)", 650, 100),
                        ("jit_train_step(11)", 1200, 100)],
        "XLA Ops": [
            ("%fusion.1 = f32[2] fusion(f32[2] %p)", 100, 200),
            ("%while.2 = (s32[]) while((s32[]) %t)", 150, 250),
            ("%bucket_probe_multi.1 = (s32[2]) custom-call(f32[8] %q)",
             660, 50),
            ("%gather_weight.3 = (s32[2]) custom-call(s32[4] %s)", 720, 20),
            ("%fusion.9 = f32[2] fusion(f32[2] %p)", 1200, 100),
        ]}
    return [("/host:CPU", host), ("/device:TPU:0", dev)]


def test_busy_idle_and_programs():
    r = tr.reduce_planes(synthetic())
    assert r.window_s == pytest.approx(1000e-9)
    # ops [100, 400] u [660, 710] u [720, 740]; the op after the window
    # does not count
    assert r.busy_s == pytest.approx(370e-9)
    assert r.module_s == {"jit_train_step": pytest.approx(300e-9),
                          "jit_sample_gather": pytest.approx(100e-9)}
    assert r.op_s["bucket_probe_multi"] == pytest.approx(50e-9)
    assert r.op_calls["gather_weight"] == 1
    assert r.op_s["fusion"] == pytest.approx(200e-9)


def test_idle_gaps_are_named_by_the_open_span():
    gaps = tr.reduce_planes(synthetic()).breakdown["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["bench/draw", "bench/step"]
    assert [g[1] for g in gaps[:2]] == [pytest.approx(260e-9)] * 2
    assert gaps[2] == ["bench/step", pytest.approx(100e-9)]
    ops = tr.reduce_planes(synthetic()).breakdown["device_ops"]
    # the while's event spans its body's ops: only the ops are listed
    assert ops[0] == ["jit_train_step/fusion.1", pytest.approx(200e-9)]
    assert "while" not in tr.reduce_planes(synthetic()).op_s


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes([synthetic()[0]])


@pytest.mark.parametrize("text, name", [
    ("%bucket_probe_multi.1 = (s32[2,29]) custom-call()", "bucket_probe_multi"),
    ("%simhash_codes = s32[2] custom-call()", "simhash_codes"),
    ("%bitcast_add_fusion.6 = bf16[4] fusion()", "bitcast_add_fusion"),
    ("%copy-start.65 = (bf16[1]) copy-start()", "copy-start"),
])
def test_op_name(text, name):
    assert tr.op_name(text) == name


def test_recorded_tpu_trace():
    """A trace recorded on a v5e: three rounds of a jitted step and the
    simhash kernel inside a 4 ms window.  The device clock there ran about
    a millisecond ahead of the host's, so the first kernel call can fall
    just outside the window."""
    r = tr.reduce_dir(os.path.dirname(FIXTURE))
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    assert r.module_s["jit_train_step"] > 0
    assert r.op_calls["simhash_codes"] in (2, 3)
    assert r.op_s["simhash_codes"] > 0
    assert {g[0] for g in r.breakdown["idle_gaps"]} <= {
        "bench/step", "bench/draw", "host outside bench spans"}
