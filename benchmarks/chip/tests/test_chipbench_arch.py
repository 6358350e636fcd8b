"""Architecture modules: a configuration's ``model_type`` picks the module
that builds, draws, checks and counts it, every key of the file is read,
fixed at the value the program computes, or descriptive, and a new
architecture is a new module with no edit to the harness."""

import glob
import importlib
import json
import math
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import chipbench_smoke as cs
from chipbench import arch, metrics_io, reference, run, spec, traffic
from repro.models import ModelConfig

CONFIGS = sorted(glob.glob(f"{cs.BENCH}/configs/*.json"))
GRANITE = f"{cs.BENCH}/configs/granite-3-8b-1chip.json"
REFRESH_CELL = "granite-lgd-refresh"


def _name(path):
    return os.path.basename(path)[:-len(".json")]


@pytest.mark.parametrize("path", CONFIGS, ids=_name)
def test_every_configuration_loads_through_its_module(path):
    conf = spec.read_json(path)
    module = arch.load(conf["model_type"])
    assert module.__name__ == f"chipbench.arch.{conf['model_type']}"
    cfg = spec.model_config(conf, _name(path))
    assert isinstance(cfg, ModelConfig)
    shapes = jax.eval_shape(lambda k: module.make_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert len(jax.tree.leaves(shapes)) == 12
    assert module.train_flops_per_step(cfg, 1, 16) > 0


def _bench_with(tmp_path, conf):
    """A copy of BENCHMARK.json whose Granite configuration is ``conf``."""
    path = tmp_path / "granite.json"
    path.write_text(json.dumps(conf))
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        if c["name"] == "granite-3-8b-1chip":
            c["file"] = str(path)
    bench_path = tmp_path / "BENCHMARK.json"
    bench_path.write_text(json.dumps(bench))
    return str(path), str(bench_path)


@pytest.mark.parametrize("key, value", [
    ("layer_types", ["mamba"] * 9 + ["attention"]),
    ("tie_word_embeddings", True),
    ("embedding_multiplier", 12),
])
def test_key_the_program_would_drop_is_refused(tmp_path, key, value):
    conf = {**spec.read_json(GRANITE), key: value}
    with pytest.raises(ValueError, match=key):
        spec.model_config(conf, "granite-3-8b-1chip")
    path, bench = _bench_with(tmp_path, conf)
    with pytest.raises(SystemExit) as e:
        spec.load_cell(REFRESH_CELL, bench)
    assert path in str(e.value) and repr(key) in str(e.value)


def test_fixed_key_at_its_value_is_accepted():
    conf = {**spec.read_json(GRANITE), "rope_scaling": None,
            "attention_multiplier": 128 ** -0.5}
    assert spec.model_config(conf, "g") == spec.model_config(
        spec.read_json(GRANITE), "g")


def test_unknown_model_type_is_refused(tmp_path):
    conf = {**spec.read_json(GRANITE), "model_type": "nosucharch"}
    with pytest.raises(ValueError, match="chipbench/arch/nosucharch.py"):
        spec.model_config(conf, "granite-3-8b-1chip")
    _, bench = _bench_with(tmp_path, conf)
    with pytest.raises(SystemExit, match="chipbench/arch/nosucharch.py"):
        spec.load_cell(REFRESH_CELL, bench)


def test_bench_stops_before_set_up_on_an_unread_key(tmp_path):
    """bench.py, run from a checkout whose Granite file gains a key, exits
    non-zero naming the key before it looks for a chip, and prints no
    result."""
    checkout = tmp_path / "checkout"
    shutil.copytree(cs.BENCH, checkout / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(cs.ROOT, "BENCHMARK.json"), checkout)
    os.symlink(os.path.join(cs.ROOT, "src"), checkout / "src")
    conf = (checkout / "benchmarks" / "chip" / "configs"
            / "granite-3-8b-1chip.json")
    conf.write_text(json.dumps({**json.loads(conf.read_text()),
                                "layer_types": ["attention"]}))
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         REFRESH_CELL, "--seed", "1", "--seconds", "1"],
        cwd=checkout, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""
    assert "'layer_types'" in out.stderr
    assert "configs/granite-3-8b-1chip.json" in out.stderr
    assert "TPU" not in out.stderr


TOY = '''"""A test-only architecture: a residual stack of SwiGLU MLPs."""
import jax
import jax.numpy as jnp

from chipbench.reference import F32, mm, rms

KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
        "vocab_size", "rms_norm_eps", "torch_dtype")
FIXED = {"tie_word_embeddings": False}


def model_config(conf, name):
    from repro.models import ModelConfig
    return ModelConfig(
        name=name, n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=1, n_kv_heads=1,
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        norm_eps=conf["rms_norm_eps"], dtype=conf["torch_dtype"])


def make_params(key, cfg):
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    ke, kh, kb = jax.random.split(key, 3)

    def block(k):
        kg, ku, kd = jax.random.split(k, 3)
        return {"norm": {"scale": jnp.ones((d,))},
                "w_gate": jax.random.normal(kg, (d, f)) * d ** -0.5,
                "w_up": jax.random.normal(ku, (d, f)) * d ** -0.5,
                "w_down": jax.random.normal(kd, (f, d)) * f ** -0.5}
    return {"embed_group": {
                "embed": jax.random.normal(ke, (v, d)) * d ** -0.5,
                "lm_head": jax.random.normal(kh, (d, v)) * d ** -0.5,
                "final_norm": {"scale": jnp.ones((d,))}},
            "blocks": [jax.vmap(block)(jax.random.split(kb, cfg.n_layers))]}


def constants(cfg):
    return (("eps", cfg.norm_eps), ("layers", cfg.n_layers))


def hidden(params, tokens, c, precision):
    x = params["embed_group"]["embed"].astype(F32)[tokens]
    for i in range(c["layers"]):
        p = jax.tree.map(lambda a: a[i].astype(F32), params["blocks"][0])
        h = rms(x, p["norm"]["scale"], c["eps"])
        u = jax.nn.silu(mm("bsd,df->bsf", h, p["w_gate"], precision)) * mm(
            "bsd,df->bsf", h, p["w_up"], precision)
        x = x + mm("bsf,fd->bsd", u, p["w_down"], precision)
    return x


def matmul_params(cfg):
    return cfg.n_layers * 3 * cfg.d_model * cfg.d_ff + cfg.d_model * cfg.vocab


def train_flops_per_step(cfg, batch, seq):
    return 6.0 * matmul_params(cfg) * batch * seq
'''


def test_new_architecture_needs_no_harness_edit(tmp_path, monkeypatch):
    """A module for a new ``model_type``, put on the package's path, is
    loaded, drawn, trained by the reference and counted by ``mfu`` with no
    file of the harness edited."""
    (tmp_path / "arch").mkdir()
    (tmp_path / "arch" / "toystack.py").write_text(TOY)
    monkeypatch.setattr(arch, "__path__",
                        [*arch.__path__, str(tmp_path / "arch")])
    monkeypatch.delitem(sys.modules, "chipbench.arch.toystack", raising=False)
    importlib.invalidate_caches()
    here = tmp_path / "bench"
    for sub, name, data in (("traffic", "toy", cs.TRAFFIC["uniform"]),
                            ("limits", "toy-cell", {"loss_gap": 1e-4})):
        (here / sub).mkdir(parents=True)
        (here / sub / f"{name}.json").write_text(json.dumps(data))
    conf = {"model_type": "toystack", "hidden_size": 32,
            "intermediate_size": 64, "num_hidden_layers": 2,
            "vocab_size": 128, "rms_norm_eps": 1e-5,
            "torch_dtype": "float32", "tie_word_embeddings": False,
            "source": "a test"}
    (tmp_path / "toy.json").write_text(json.dumps(conf))
    bench = {"configs": [{"name": "toy", "file": str(tmp_path / "toy.json")}],
             "workloads": [{"name": "toy-cell", "config": "toy",
                            "traffic": "toy", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "HERE", str(here))
    try:
        cell = spec.load_cell("toy-cell", str(tmp_path / "BENCHMARK.json"))
        assert cell.arch.__name__ == "chipbench.arch.toystack"
        cfg = spec.model_config(cell.config, cell.config_name)
        params = cell.arch.make_params(jax.random.PRNGKey(0), cfg)
        tokens = np.asarray(traffic.corpus_for(3, cfg, cell.traffic)[0])
        batches = [{"tokens": tokens[2 * i:2 * i + 2, :-1],
                    "targets": tokens[2 * i:2 * i + 2, 1:]}
                   for i in range(3)]
        losses, grads, change = reference.train_steps(
            cell.arch, params, batches, cfg, run._opt(cell.traffic))
        record = SimpleNamespace(cell=cell, cfg=cfg, traffic=cell.traffic,
                                 steps=10, window_s=2.0, chips=1,
                                 device_kind="TPU v5 lite")
        mfu = metrics_io.reader("mfu")(record)
    finally:
        sys.modules.pop("chipbench.arch.toystack", None)
    assert losses.shape == (3,) and np.all(np.isfinite(losses))
    assert abs(losses[0] - math.log(128)) < 0.5
    assert len(grads) == len(change) == len(jax.tree.leaves(params)) == 7
    assert np.all(grads > 0) and np.all(change > 0)
    per_step = 6.0 * (2 * 3 * 32 * 64 + 32 * 128) * 2 * 32
    assert mfu == pytest.approx(100 * per_step * 10 / (2.0 * 197e12))


# The reference's readings at Granite smoke size, recorded from the harness
# at commit cb9e4f4 (before the architecture modules); the move changes
# none of them.
PINNED = {
    "losses": [5.368340492248535, 5.56767463684082, 5.392739295959473],
    "grad_norms": [
        0.0522940531373024, 0.17127875983715057, 0.6354543566703796,
        0.1727265566587448, 0.30425554513931274, 0.03929785639047623,
        0.3563425540924072, 0.22754886746406555, 0.22928033769130707,
        0.39997273683547974, 0.03774777427315712, 0.21782110631465912],
    "change_norms": [
        0.0018810678739100695, 0.010943116620182991, 0.016856243833899498,
        0.01584203913807869, 0.011975523084402084, 0.0021444738376885653,
        0.02278650738298893, 0.022775869816541672, 0.02287881262600422,
        0.0102097038179636, 0.0017659555887803435, 0.017221687361598015],
    "pooled_norms": [4.803133487701416, 4.218203544616699],
    "pooled_cols": [  # columns 0, 7, 31 and 63 of the two rows
        [-0.36916762590408325, 0.4617163836956024, 0.07308749109506607,
         0.0032240264117717743],
        [-0.10048645734786987, -0.10100311785936356, -0.4680643677711487,
         0.4731051027774811]],
}


@pytest.fixture(scope="module")
def smoke_inputs():
    cell = cs.cell("lgd")
    cfg = spec.model_config(cell.config, cell.config_name)
    params = cell.arch.make_params(jax.random.PRNGKey(5), cfg)
    tokens, _ = traffic.make_corpus(
        traffic.stream(5, traffic.CORPUS), rows=8, seq=32, vocab=cfg.vocab,
        zipf=1.1, hard_frac=0.1)
    return cell, cfg, params, np.asarray(tokens)


def test_train_steps_equal_the_parent_harness(smoke_inputs):
    cell, cfg, params, tokens = smoke_inputs
    w = np.asarray([0.75, 1.25], np.float32)
    batches = [{"tokens": tokens[2 * i:2 * i + 2, :-1],
                "targets": tokens[2 * i:2 * i + 2, 1:], "loss_weights": w}
               for i in range(3)]
    got = reference.train_steps(cell.arch, params, batches,
                                cfg, run._opt(cell.traffic))
    for name, value in zip(("losses", "grad_norms", "change_norms"), got):
        np.testing.assert_allclose(value, PINNED[name], rtol=1e-6,
                                   err_msg=name)


def test_pooled_rows_equal_the_parent_harness(smoke_inputs):
    cell, cfg, params, tokens = smoke_inputs
    rows = run.pooled_rows(cell.arch, jax.device_get(params), tokens[:2, :-1],
                           cfg, "float32")
    np.testing.assert_allclose(np.linalg.norm(rows, axis=1),
                               PINNED["pooled_norms"], rtol=1e-6)
    np.testing.assert_allclose(rows[:, [0, 7, 31, 63]], PINNED["pooled_cols"],
                               rtol=1e-6)
