"""Granite-4.0-H's hybrid in the chip harness: its configuration file is the
program's ``ONE_CHIP``, its architecture module (``arch/granitemoehybrid.py``)
draws the program's weights and computes what the program computes, its
``correct`` passes the program and fails a lower precision, its FLOPs are
counted from shapes, and the device time under the program's ``mamba2``
scopes is read from a trace's op metadata (``chipbench/scopes.py``)."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_smoke as cs
from chipbench import arch, check, metrics_io, reference, run, scopes, spec
from repro.configs import granite_4_0_h_micro
from repro.dist.sharding import use_mesh
from repro.launch.mesh import make_host_mesh
from repro.models import forward, init_params, loss

NAME = "granite-4.0-h-micro-1chip"
FILE = f"{cs.BENCH}/configs/{NAME}.json"
CELL = "granite4h-uniform-8k"
MODULE = arch.load("granitemoehybrid")
SEED = 2 ** 31 + 91

# one period of Mamba-2, Mamba-2, attention, twice, at toy widths
SMOKE = {
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "shared_intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2, "vocab_size": 128,
    "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "mamba_d_state": 16,
    "mamba_d_head": 16, "mamba_expand": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 16, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 1 / 64,
    "torch_dtype": "float32",
    "execution": {"attn_block_q": 16, "loss_chunk": 16},
}


def smoke_cell(dtype, limits):
    return spec.Cell(
        name="smoke-hybrid", chips=1, config_name="smoke-hybrid",
        config={**SMOKE, "torch_dtype": dtype}, arch=MODULE,
        traffic_name="uniform", traffic=cs.TRAFFIC["uniform"],
        end_to_end=cs.METRICS, per_layer=[], limits=limits)


def test_configuration_file_is_the_program_config():
    got = spec.model_config(spec.read_json(FILE), NAME)
    assert got == granite_4_0_h_micro.ONE_CHIP.with_(name=NAME)
    assert got.block_pattern == granite_4_0_h_micro.PERIOD


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weights_equal_init_params(dtype):
    cfg = granite_4_0_h_micro.SMOKE.with_(dtype=dtype)
    key = jax.random.PRNGKey(7)
    got, want = MODULE.make_params(key, cfg), init_params(key, cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weights_match_layout_at_real_size():
    cfg = spec.model_config(spec.read_json(FILE), NAME)
    key = jax.random.PRNGKey(0)
    got = jax.eval_shape(lambda k: MODULE.make_params(k, cfg), key)
    want = jax.eval_shape(lambda k: init_params(k, cfg), key)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)
    # one period and an eighth of the vocabulary, embedding and head untied
    assert sum(x.size for x in jax.tree.leaves(got)) == 797_850_560


def test_forward_and_loss_equal_the_program():
    """On seeded weights in float32 the reference's forward (the SSD in its
    quadratic form, the conv as shifted sums) and loss equal the program's
    (chunked scan, conv by the same sums) to float32 round-off: 1.3e-7 of
    the hidden state's norm, read on the CPU.  The tolerance, 1e-5, is 80
    times that and under a hundredth of what the bfloat16-rounded
    reference reads there (1.5e-3), so a dropped or altered term fails it."""
    cfg = spec.model_config(SMOKE, "smoke-hybrid")
    params = MODULE.make_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0, cfg.vocab)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
             "loss_weights": jnp.asarray([0.75, 1.25])}
    c = dict(MODULE.constants(cfg))
    want = MODULE.hidden(params, batch["tokens"], c, "float32")
    got = forward(params, cfg, batch)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5
    lower = MODULE.hidden(params, batch["tokens"], c, "bfloat16")
    assert float(jnp.linalg.norm(lower - want) / jnp.linalg.norm(want)) > 1e-4
    np.testing.assert_allclose(
        float(loss(params, cfg, batch)),
        float(reference.loss(MODULE, params, batch, c, "float32")),
        rtol=1e-5)


@pytest.fixture(scope="module")
def smoke_readings():
    """The program's readings of the float32 and bfloat16 smoke cells."""
    out = {}
    mesh = make_host_mesh()
    for dtype in ("float32", "bfloat16"):
        cell = smoke_cell(dtype, {})
        cfg = spec.model_config(cell.config, cell.config_name)
        with use_mesh(mesh):
            tr, prog, _ = run.set_up(cell, SEED, mesh)
            run.close(tr, prog)
            del tr
            out[dtype] = (cell, cfg, prog)
    return out


def _numbers(smoke_readings, dtype, control=None):
    cell, cfg, prog = smoke_readings[dtype]
    with use_mesh(make_host_mesh()):
        return run.reference_numbers(cell, cfg, SEED, prog, control)


def test_float32_program_is_correct_and_bfloat16_control_is_not(
        smoke_readings):
    """In float32 the program reads under 1e-6 in every gap (seeds 0-2 on
    the CPU), the bfloat16 control 2.6e-5 and over; limits of 1e-5."""
    limits = {"loss_gap": 1e-5, "grad_gap": 1e-5, "change_gap": 1e-5,
              "rows_mismatch": 0}
    assert check.judge(_numbers(smoke_readings, "float32"), limits)[0]
    control = _numbers(smoke_readings, "float32", "bfloat16")
    assert not check.judge(control, limits)[0]
    for k in ("grad_gap", "change_gap"):
        assert control[k] > limits[k], (k, control[k])


def test_float8_control_fails_at_smoke_size(smoke_readings):
    """In bfloat16, the configuration's precision, the program reads
    loss_gap up to 1.8e-4 and grad_gap up to 1.8e-3 (seeds 0-1 on the CPU),
    the float8 control 9e-4 and 9e-3 and over: limits of 5e-4 and 5e-3."""
    limits = {"loss_gap": 5e-4, "grad_gap": 5e-3, "rows_mismatch": 0}
    assert check.judge(_numbers(smoke_readings, "bfloat16"), limits)[0]
    control = _numbers(smoke_readings, "bfloat16", "float8")
    for k in ("loss_gap", "grad_gap"):
        assert control[k] > limits[k], (k, control[k])


def test_flops_from_shapes():
    cfg = spec.model_config(spec.read_json(FILE), NAME)
    d, ff, v = 2048, 8192, 12544
    mamba = d * (2 * 4096 + 2 * 128 + 64) + 4096 * d
    attn = d * 64 * (2 * 32 + 2 * 8)
    assert MODULE.matmul_params(cfg) == 9 * mamba + attn + 10 * 3 * d * ff \
        + d * v
    assert MODULE.matmul_params(cfg) == pytest.approx(771.9e6, rel=1e-4)
    # the SSD's chunked form, forward, per sequence and Mamba-2 layer
    ssd = 8192 * (256 * 128 + 256 * 64 * 64 + 4 * 64 * 128 * 64)
    assert MODULE.ssd_flops(cfg, 8192) == ssd
    per_step = MODULE.train_flops_per_step(cfg, 1, 8192)
    assert per_step == 6 * MODULE.matmul_params(cfg) * 8192 \
        + 6 * 8192 ** 2 * 32 * 64 + 3 * ssd * 9
    assert per_step == pytest.approx(39.47e12, rel=1e-3)


@pytest.mark.parametrize("key, value", [
    ("mamba_proj_bias", True),
    ("mamba_n_groups", 8),
    ("logits_scaling", 8),
])
def test_unread_key_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        spec.model_config({**spec.read_json(FILE), key: value}, NAME)


def test_bench_stops_before_set_up_on_an_unread_key(tmp_path):
    """bench.py, run from a checkout whose hybrid file gains
    ``mamba_proj_bias: true``, exits non-zero naming the key before it
    looks for a chip, and prints no result."""
    checkout = tmp_path / "checkout"
    shutil.copytree(cs.BENCH, checkout / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(cs.ROOT, "BENCHMARK.json"), checkout)
    os.symlink(os.path.join(cs.ROOT, "src"), checkout / "src")
    conf = checkout / "benchmarks" / "chip" / "configs" / f"{NAME}.json"
    conf.write_text(json.dumps({**json.loads(conf.read_text()),
                                "mamba_proj_bias": True}))
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1"],
        cwd=checkout, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""
    assert "'mamba_proj_bias'" in out.stderr
    assert f"configs/{NAME}.json" in out.stderr
    assert "TPU" not in out.stderr


# -- scopes ------------------------------------------------------------------

PREFIX = "jit(train_step)/transpose(jvp(checkpoint))"


def write_trace(path, planes):
    """An XSpace with one TPU plane per entry of ``planes``: each a list of
    (line, event name, tf_op path or None, start_ns, duration_ns)."""
    pb2 = scopes._xplane_pb2()
    space = pb2.XSpace()
    for n, events in enumerate(planes):
        plane = space.planes.add(name=f"/device:TPU:{n}")
        plane.stat_metadata[1].id = 1
        plane.stat_metadata[1].name = "tf_op"
        lines = {}
        for i, (line, name, tf_op, start, dur) in enumerate(events, 1):
            md = plane.event_metadata[i]
            md.id, md.name = i, name
            if tf_op is not None:
                md.stats.add(metadata_id=1, str_value=tf_op + ":")
            if line not in lines:
                lines[line] = plane.lines.add(name=line, timestamp_ns=1000)
            lines[line].events.add(metadata_id=i, offset_ps=start * 1000,
                                   duration_ps=dur * 1000)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def events(shift=0):
    mark = "jit_bench_window_mark(7)"
    return [
        ("XLA Modules", mark, None, 0, 10),
        ("XLA Modules", mark, None, 1000, 10),
        ("XLA Modules", "jit_train_step(3)", None, 20, 900),
        # a while loop under the SSD spans two of its body's ops
        ("XLA Ops", "%while.1", f"{PREFIX}/mamba2/ssd/while", 100, 200),
        ("XLA Ops", "%fusion.2", f"{PREFIX}/mamba2/ssd/while/body/mul",
         110, 50 + shift),
        ("XLA Ops", "%fusion.3", f"{PREFIX}/mamba2/ssd/dot_general", 250, 40),
        ("XLA Ops", "%fusion.4",
         f"{PREFIX}/rematted_computation/mamba2/conv/add", 400, 30),
        ("XLA Ops", "%fusion.5", f"{PREFIX}/mamba2/gate_norm/mul", 500, 20),
        ("XLA Ops", "%fusion.6", f"{PREFIX}/ffn/dot_general", 600, 100),
        # before the window: not counted
        ("XLA Ops", "%fusion.7", f"{PREFIX}/mamba2/ssd/mul", 2, 5),
    ]


def test_scope_seconds_read_the_op_metadata(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path, [events()])
    planes = scopes.read_ops(path)
    assert scopes.scope_seconds(planes, "mamba2/ssd") == pytest.approx(200e-9)
    assert scopes.scope_seconds(planes, "mamba2") == pytest.approx(250e-9)
    assert scopes.scope_seconds(planes, "ssd") == pytest.approx(200e-9)
    assert scopes.scope_seconds(planes, "conv") == pytest.approx(30e-9)
    assert scopes.scope_seconds(planes, "ssd/mamba2") == 0
    # two chips: the mean
    write_trace(path, [events(), events(shift=300)])
    assert scopes.scope_seconds(scopes.read_ops(path), "mamba2/ssd") == \
        pytest.approx((200e-9 + 360e-9) / 2)


def test_scope_seconds_need_the_window_marks(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path, [events()[2:]])
    assert scopes.scope_seconds(scopes.read_ops(path), "mamba2") is None


@pytest.mark.parametrize("component, name", [
    ("transpose(jvp(mamba2))", "mamba2"), ("mamba2", "mamba2"),
    ("jit(<lambda>)", "<lambda>"), ("checkpoint", "checkpoint"),
])
def test_bare(component, name):
    assert scopes.bare(component) == name


def test_metrics_read_the_scopes_or_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path))
    record = SimpleNamespace(trace=object(), steps=2)
    write_trace(str(tmp_path / "plugins" / "t.xplane.pb"), [events()])
    mixer = metrics_io.reader("mamba_mixer_device_ms")(record)
    ssd = metrics_io.reader("ssd_device_ms")(record)
    assert mixer == pytest.approx(1e3 * 250e-9 / 2)
    assert ssd == pytest.approx(1e3 * 200e-9 / 2)
    # a program without the scopes, or an untraced run, gives nothing
    write_trace(str(tmp_path / "plugins" / "t.xplane.pb"),
                [events()[:3] + events()[-4:-3]])
    assert metrics_io.reader("ssd_device_ms")(
        SimpleNamespace(trace=object(), steps=2)) is None
    assert metrics_io.reader("mamba_mixer_device_ms")(
        SimpleNamespace(trace=None, steps=2)) is None
