"""``correct`` at smoke size on the CPU: the timed path passes against the
plain reference, the control one precision lower fails, and a run whose
timed path is broken underneath comes out not correct."""

import time

import jax
import pytest

import chipbench_smoke as cs
from chipbench import check, run, spec
from repro.dist.sharding import use_mesh
from repro.launch.mesh import make_host_mesh

SEED = 2 ** 31 + 77


def run_smoke(kind, seed=SEED):
    return run.run_cell(cs.cell(kind), seed, 0.2, False,
                        time.perf_counter(), None)


@pytest.mark.parametrize("kind", ["lgd", "uniform"])
def test_sound_run_is_correct(kind):
    out = run_smoke(kind)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cs.cell(kind).limits)
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_bfloat16_control_fails():
    cell = cs.cell("lgd")
    cfg = spec.model_config(cell.config, cell.config_name)
    mesh = make_host_mesh()
    with use_mesh(mesh):
        tr, prog, _ = run.set_up(cell, SEED, mesh)
        run.close(tr, prog)
        del tr
        sound = run.reference_numbers(cell, cfg, SEED, prog)
        control = run.reference_numbers(cell, cfg, SEED, prog, "bfloat16")
    assert check.judge(sound, cell.limits)[0]
    assert not check.judge(control, cell.limits)[0]
    for k in ("loss_gap", "grad_gap", "change_gap", "weight_gap",
              "feature_gap"):
        assert control[k] > cell.limits[k], (k, control[k])


def _frozen_state(monkeypatch):
    import repro.train.trainer as trainer
    monkeypatch.setattr(trainer, "apply_updates", lambda p, u: p)


def _half_batch(monkeypatch):
    import repro.train.trainer as trainer
    loss = trainer.lm_loss

    def half(params, cfg, batch):
        return loss(params, cfg, jax.tree.map(
            lambda x: x[: x.shape[0] // 2], batch))
    monkeypatch.setattr(trainer, "lm_loss", half)


def _altered_token(monkeypatch):
    import repro.data.lsh_pipeline as pipeline
    draw = pipeline.sample_gather

    def altered(*a, **kw):
        gb = draw(*a, **kw)
        return gb._replace(tokens=gb.tokens.at[0, 3].add(1))
    monkeypatch.setattr(pipeline, "sample_gather", altered)


def _altered_weight(monkeypatch):
    import repro.data.lsh_pipeline as pipeline
    draw = pipeline.sample_gather

    def altered(*a, **kw):
        gb = draw(*a, **kw)
        return gb._replace(loss_weights=gb.loss_weights.at[0].multiply(1.5))
    monkeypatch.setattr(pipeline, "sample_gather", altered)


@pytest.mark.parametrize("fault, caught_by", [
    (_frozen_state, "change_gap"),
    (_half_batch, "loss_gap"),
    (_half_batch, "grad_gap"),
    (_altered_token, "rows_mismatch"),
    (_altered_token, "loss_gap"),
    (_altered_weight, "loss_gap"),
])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, caught_by):
    jax.clear_caches()
    fault(monkeypatch)
    out = run_smoke("lgd")
    jax.clear_caches()
    assert not out["correct"]
    c = out["checks"][caught_by]
    assert c["value"] > c["limit"]
