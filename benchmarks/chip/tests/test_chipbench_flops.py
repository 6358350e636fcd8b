"""FLOP and byte counts of the chip benchmark, from shapes alone: a model's
from its architecture module, a kernel's from ``chipbench/flops.py``."""

import json

import pytest

import chipbench_smoke as cs
from chipbench import arch, flops, spec


def config(name):
    """(architecture module, ModelConfig) of a configuration file."""
    conf = spec.read_json(f"{cs.BENCH}/configs/{name}.json")
    return arch.load(conf["model_type"]), spec.model_config(conf, name)


@pytest.mark.parametrize("name, n_matmul", [
    ("granite-3-8b-1chip", 400.5e6),
    ("phi4-mini-1chip", 254.3e6),
])
def test_matmul_params(name, n_matmul):
    module, cfg = config(name)
    assert module.matmul_params(cfg) == pytest.approx(n_matmul, rel=1e-3)


def test_train_flops_granite_step():
    # 6 N T plus causal attention: about 20.5 TFLOP per 2 x 4096 step
    module, cfg = config("granite-3-8b-1chip")
    per_step = module.train_flops_per_step(cfg, 2, 4096)
    attn = 6 * 4096 ** 2 * 32 * 128 * 2
    assert per_step == 6 * module.matmul_params(cfg) * 8192 + attn
    assert per_step == pytest.approx(20.5e12, rel=0.01)


def test_kernel_counts():
    ops, nbytes = flops.simhash(1024, 4096, 7, 10)
    assert ops == 2 * 1024 * 4096 * 70
    assert nbytes == 4 * (1024 * 4096 + 4096 * 70 + 1024 * 10)
    ops, nbytes = flops.bucket_probe(1, 4096, 7, 10, 1024, 29)
    assert ops == 2 * 4096 * 70 + 2 * 29 * 10 * 1024
    assert nbytes == 4 * (4096 + 4096 * 70 + 10 * 1024 + 2 * 29 * 10)


def test_roofline_share_names_its_bound():
    peak = flops.peaks("TPU v5 lite")
    share, bound = flops.roofline_share(197e12, 1.0, 2.0, peak)
    assert (share, bound) == (pytest.approx(50.0), "compute")
    share, bound = flops.roofline_share(1.0, 819e9, 4.0, peak)
    assert (share, bound) == (pytest.approx(25.0), "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_peaks_table_has_its_source():
    table = json.load(open(flops.PEAKS_FILE))
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
