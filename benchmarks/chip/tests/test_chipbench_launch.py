"""The benchmark builds the system under test as the launcher does.

``repro.launch.train.train`` fixes its corpus and keys; at those same
inputs the benchmark's weights equal ``init_params``' and its trainer takes
``train()``'s first-step loss.  A change to the launcher that the
benchmark does not follow fails here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_smoke as cs
from chipbench import build, spec, traffic
from chipbench.arch import granite
from repro.configs import granite_3_8b, phi4_mini_3_8b
from repro.data import make_token_corpus
from repro.dist.sharding import tree_param_shardings, use_mesh
from repro.launch.mesh import make_host_mesh
from repro.launch.train import train
from repro.models import init_params


@pytest.mark.parametrize("name, expected", [
    ("granite-3-8b-1chip", granite_3_8b.ONE_CHIP),
    ("phi4-mini-1chip", phi4_mini_3_8b.FULL.with_(
        name="phi4-mini-1chip", n_layers=1, vocab=50016)),
])
def test_configuration_files_are_the_program_configs(name, expected):
    got = spec.model_config(
        spec.read_json(f"{cs.BENCH}/configs/{name}.json"), name)
    assert got == expected.with_(name=name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weights_equal_init_params(dtype):
    cfg = granite_3_8b.SMOKE.with_(dtype=dtype)
    key = jax.random.PRNGKey(7)
    got, want = granite.make_params(key, cfg), init_params(key, cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weights_match_layout_at_real_size():
    cfg = granite_3_8b.ONE_CHIP
    key = jax.random.PRNGKey(0)
    got = jax.eval_shape(lambda k: granite.make_params(k, cfg), key)
    want = jax.eval_shape(lambda k: init_params(k, cfg), key)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)


def test_seed_keys_past_32_bits_differ():
    keys = {tuple(np.asarray(jax.random.key_data(traffic.root_key(s))))
            for s in (5, 2 ** 32 + 5, 2 ** 33 + 5, 2 ** 31 + 5)}
    assert len(keys) == 4


def test_corpus_is_seeded_and_in_vocab():
    a, ha = traffic.make_corpus(traffic.stream(3, traffic.CORPUS), rows=64,
                                seq=32, vocab=128, zipf=1.1, hard_frac=0.1)
    b, _ = traffic.make_corpus(traffic.stream(3, traffic.CORPUS), rows=64,
                               seq=32, vocab=128, zipf=1.1, hard_frac=0.1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (64, 33) and a.dtype == jnp.int32
    assert 0 <= int(a.min()) and int(a.max()) < 128
    # Zipf: token 0 is the most common outside the hard rows
    easy = np.asarray(a)[~np.asarray(ha)]
    assert np.bincount(easy.ravel(), minlength=128).argmax() == 0


@pytest.mark.parametrize("lgd", [True, False])
def test_first_step_loss_equals_train(lgd):
    cfg = granite_3_8b.SMOKE
    batch, seq, corpus = 2, 16, 64
    mesh = make_host_mesh()
    want = train(cfg, steps=0, batch=batch, seq=seq, corpus=corpus,
                 lgd=lgd, multiprobe=28, mesh=mesh).run(1)["losses"][0]

    mix = {"sampler": "lgd" if lgd else "uniform", "seq": seq,
           "batch": batch, "corpus_rows": corpus, "k": 7, "l": 10,
           "multiprobe": 28, "refresh_every": 200, "refresh_lead": 1,
           "lr": 1e-3, "warmup_steps": 10, "schedule_steps": 0}
    data = make_token_corpus(0, corpus, seq, cfg.vocab)
    with use_mesh(mesh):
        params = granite.make_params(jax.random.PRNGKey(0), cfg)
        shardings = tree_param_shardings(params, mesh)
        params = jax.tree.map(jax.device_put, params, shardings)
        tr = build.build_trainer(
            cfg, mix, data.tokens, data.hard_mask, params, mesh,
            pipeline_key=jax.random.PRNGKey(2), uniform_seed=1)
        got = tr.run(1)["losses"][0]
    tr.finalize()
    assert got == want
