"""End-to-end sharded LGD: weight composition, unbiasedness, overlapped
refresh determinism, elastic reshard-on-restore, Trainer sampler hook."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import (
    LSHPipelineConfig,
    ShardedLSHPipeline,
    lm_head_query_fn,
    make_token_corpus,
    mean_pool_feature_fn,
)
from repro.dist.sharding import example_shard_bounds
from repro.models import ModelConfig, init_params
from repro.optim import Adam
from repro.train import Trainer, TrainerConfig
from repro.train.elastic import rebuild_sharded_pipeline

KEY = jax.random.PRNGKey(0)
VOCAB, DIM = 50, 16
EMBED = jax.random.normal(jax.random.PRNGKey(1), (VOCAB, DIM))
PARAMS = {"embed": EMBED, "q": jnp.ones((DIM,))}


def _tokens(n=128, seq=9, seed=2):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n, seq), 0, VOCAB),
        np.int32)


def feature_fn(params, chunk):              # toy params-aware embedding
    return jnp.mean(params["embed"][chunk], axis=1)


def query_fn(params):
    return params["q"]


def _pipe(tokens=None, n_shards=4, minibatch=16, refresh_every=6, **kw):
    cfg = LSHPipelineConfig(k=4, l=8, minibatch=minibatch,
                            refresh_every=refresh_every, **kw)
    return ShardedLSHPipeline(
        jax.random.PRNGKey(7), tokens if tokens is not None else _tokens(),
        feature_fn, query_fn, cfg, n_shards=n_shards, params=PARAMS)


class TestShardBounds:
    @pytest.mark.parametrize("n,s", [(128, 4), (130, 4), (7, 3), (5, 5)])
    def test_bounds_partition_corpus(self, n, s):
        spans = [example_shard_bounds(n, i, s) for i in range(s)]
        assert spans[0][0] == 0 and spans[-1][1] == n
        for (lo_a, hi_a), (lo_b, _) in zip(spans, spans[1:]):
            assert hi_a == lo_b          # contiguous, disjoint
        sizes = [hi - lo for lo, hi in spans]
        assert max(sizes) - min(sizes) <= 1


class TestShardedBatches:
    def test_global_batch_well_formed(self):
        pipe = _pipe()
        b = pipe.next_batch()
        assert b["tokens"].shape == (16, 8)
        assert b["targets"].shape == (16, 8)
        assert b["shard_ids"].shape == (16,)
        # sub-batches are contiguous: shard s owns rows [4s, 4s+4)
        assert np.array_equal(np.asarray(b["shard_ids"]),
                              np.repeat(np.arange(4), 4))
        # example_ids are GLOBAL and land inside each owner shard's span
        ids = np.asarray(b["example_ids"])
        for s in range(4):
            lo, hi = example_shard_bounds(128, s, 4)
            chunk = ids[np.asarray(b["shard_ids"]) == s]
            assert np.all((chunk >= lo) & (chunk < hi))
        assert float(jnp.mean(b["loss_weights"])) == pytest.approx(
            1.0, rel=1e-4)

    def test_per_shard_means_average_to_global_mean_exactly(self):
        """Composition identity (deterministic, per batch): the plain
        mean of the composed global weights w = S/(p N) times v over the
        whole batch EQUALS the average over shards of the per-shard
        weighted means taken with the LOCAL weights 1/(p n_s) scaled by
        n_s S / N — i.e. per-shard weighted means average to the
        full-corpus weighted mean, which is what the DP all-reduce of
        per-device means computes."""
        tokens = _tokens(n=96, seed=3)
        v = np.asarray(
            jnp.mean(EMBED[tokens[:, :-1]], axis=(1, 2))) + 2.0  # (N,)
        pipe = _pipe(tokens=tokens, n_shards=4, minibatch=16,
                     refresh_every=0, normalize_weights=False)
        n, s_count = 96, 4
        for _ in range(5):
            b = pipe.next_batch()
            w = np.asarray(b["loss_weights"], np.float64)
            ids = np.asarray(b["example_ids"])
            sh = np.asarray(b["shard_ids"])
            global_est = np.mean(w * v[ids])
            per_shard = []
            for s in range(s_count):
                lo, hi = example_shard_bounds(n, s, s_count)
                m = sh == s
                local_w = w[m] * n / ((hi - lo) * s_count)  # 1/(p n_s)
                per_shard.append(
                    np.mean(local_w * v[ids[m]]) * (hi - lo) * s_count / n)
            np.testing.assert_allclose(global_est, np.mean(per_shard),
                                       rtol=1e-9)

    @pytest.mark.statistical
    def test_sharded_estimator_unbiased(self):
        """Sharding must add NO bias: the sharded estimator's mean
        matches the unsharded Algorithm-1 estimator's mean over the same
        corpus within sampling noise, and both land on the true corpus
        mean up to the documented finite-L approximation (the reported
        p uses the analytic cp^K, the L->inf idealisation of the
        realised table ensemble — the same calibration note as
        tests/test_estimator.py)."""
        tokens = _tokens(n=96, seed=3)
        v = np.asarray(
            jnp.mean(EMBED[tokens[:, :-1]], axis=(1, 2))) + 2.0  # (N,)
        truth = float(v.mean())

        def estimate(n_shards, draws=300):
            cfg = LSHPipelineConfig(k=3, l=64, minibatch=16,
                                    refresh_every=0,
                                    normalize_weights=False)
            pipe = ShardedLSHPipeline(
                jax.random.PRNGKey(7), tokens, feature_fn, query_fn, cfg,
                n_shards=n_shards, params=PARAMS)
            es = []
            for _ in range(draws):
                b = pipe.next_batch()
                w = np.asarray(b["loss_weights"], np.float64)
                es.append(np.mean(w * v[np.asarray(b["example_ids"])]))
            return np.mean(es), np.std(es) / np.sqrt(len(es))

        est_1, sem_1 = estimate(n_shards=1)
        est_4, sem_4 = estimate(n_shards=4)
        # sharded == unsharded within noise (no sharding bias)
        assert abs(est_4 - est_1) < 5 * np.hypot(sem_1, sem_4), \
            (est_1, est_4, sem_1, sem_4)
        # both track the true mean in this calibrated regime
        assert abs(est_4 - truth) / truth < 0.10, (est_4, truth)
        assert abs(est_1 - truth) / truth < 0.10, (est_1, truth)

    def test_minibatch_must_divide_by_shards(self):
        with pytest.raises(ValueError):
            _pipe(n_shards=3, minibatch=16)


class TestDeviceResidentBatches:
    def test_next_batch_never_touches_host_numpy(self, monkeypatch):
        """The per-step path must be pure device work: a batch draw that
        calls ANY host-numpy function fails this test, and every emitted
        field must be a jax.Array (not a host ndarray)."""
        import repro.data.lsh_pipeline as L
        pipe = _pipe(refresh_every=0)
        pipe.next_batch()                  # warm up compile caches
        monkeypatch.setattr(L, "np", _NumpyGuardModule())
        b = pipe.next_batch()
        for k, v in b.items():
            assert isinstance(v, jax.Array), (k, type(v))
            assert not isinstance(v, np.ndarray), k

    def test_refresh_boundary_also_numpy_free(self, monkeypatch):
        """Crossing a (sync, full) refresh boundary stays off host numpy."""
        import repro.data.lsh_pipeline as L
        pipe = _pipe(refresh_every=2)
        for _ in range(2):
            pipe.next_batch()
        monkeypatch.setattr(L, "np", _NumpyGuardModule())
        pipe.next_batch()                  # step 2: refresh fires here

    def test_single_pipeline_batch_is_device_resident(self, monkeypatch):
        from repro.data import LSHSampledPipeline
        import repro.data.lsh_pipeline as L
        pipe = LSHSampledPipeline(
            jax.random.PRNGKey(3), _tokens(n=64), feature_fn, query_fn,
            LSHPipelineConfig(k=4, l=8, minibatch=8, refresh_every=0),
            params=PARAMS)
        pipe.next_batch()
        monkeypatch.setattr(L, "np", _NumpyGuardModule())
        b = pipe.next_batch()
        assert all(isinstance(v, jax.Array) for v in b.values())
        multi = pipe.next_batch_multi(jnp.stack([PARAMS["q"], -PARAMS["q"]]))
        assert len(multi) == 2
        assert all(isinstance(v, jax.Array)
                   for m in multi for v in m.values())


class _NumpyGuardModule:
    def __getattr__(self, name):
        raise AssertionError(
            f"host numpy.{name} called inside the step path")


class TestDeltaRefresh:
    def test_all_dirty_delta_bitwise_equals_full_refresh(self):
        """refresh(full=False) with every row dirty must produce the
        bit-exact index and features of refresh(full=True)."""
        tokens = _tokens(n=128, seed=8)
        cfg = LSHPipelineConfig(k=4, l=8, minibatch=8, refresh_every=0,
                                refresh_mode="delta", drift_frac=0.0)
        from repro.data import LSHSampledPipeline
        a = LSHSampledPipeline(jax.random.PRNGKey(4), tokens, feature_fn,
                               query_fn, cfg, params=PARAMS)
        b = LSHSampledPipeline(jax.random.PRNGKey(4), tokens, feature_fn,
                               query_fn, cfg, params=PARAMS)
        a._dirty = jnp.ones((a.n,), jnp.bool_)     # mark ALL rows dirty
        a.refresh(full=False)
        b.refresh(full=True)
        assert a._refresh_count == b._refresh_count == 1
        np.testing.assert_array_equal(np.asarray(a.index.order),
                                      np.asarray(b.index.order))
        np.testing.assert_array_equal(np.asarray(a.index.sorted_codes),
                                      np.asarray(b.index.sorted_codes))
        np.testing.assert_array_equal(np.asarray(a.features),
                                      np.asarray(b.features))

    def test_delta_mode_draws_match_full_mode_when_features_static(self):
        """With params-independent features a delta refresh is an index
        no-op (codes unchanged -> every row keeps its slot), so delta-
        and full-mode pipelines draw bit-identical batch sequences
        across refresh boundaries."""
        full = _pipe(refresh_every=5)
        delta = _pipe(refresh_every=5, refresh_mode="delta",
                      drift_frac=0.25)
        for _ in range(17):
            bf, bd = full.next_batch(), delta.next_batch()
            np.testing.assert_array_equal(np.asarray(bf["example_ids"]),
                                          np.asarray(bd["example_ids"]))
            np.testing.assert_array_equal(np.asarray(bf["loss_weights"]),
                                          np.asarray(bd["loss_weights"]))
        assert all(p._refresh_count >= 3 for p in delta.shards)

    def test_restored_delta_pipeline_replays_uninterrupted_run(self):
        """fold_in-salt contract under delta refresh: a pipeline rebuilt
        at step t (canonical build + empty dirty mask) draws the exact
        batches of the uninterrupted delta-mode run, params unchanged —
        every delta refresh re-hashes to identical codes, so both order
        chains stay at the canonical layout."""
        tokens = _tokens(n=120, seed=9)
        cfg = LSHPipelineConfig(k=4, l=8, minibatch=8, refresh_every=4,
                                refresh_mode="delta", drift_frac=0.3)
        live = ShardedLSHPipeline(jax.random.PRNGKey(15), tokens,
                                  feature_fn, query_fn, cfg, n_shards=2,
                                  params=PARAMS)
        for _ in range(9):                 # crosses two refresh boundaries
            live.next_batch()
        restored = rebuild_sharded_pipeline(
            jax.random.PRNGKey(15), tokens, feature_fn, query_fn, cfg,
            step=9, n_shards=2, params=PARAMS)
        assert all(p._refresh_count == 2 for p in restored.shards)
        for _ in range(8):                 # crosses another boundary
            bl, br = live.next_batch(), restored.next_batch()
            np.testing.assert_array_equal(np.asarray(bl["example_ids"]),
                                          np.asarray(br["example_ids"]))
            np.testing.assert_array_equal(np.asarray(bl["loss_weights"]),
                                          np.asarray(br["loss_weights"]))

    def test_async_delta_refresh_is_deterministic(self):
        """Two async delta pipelines (same key) stay bitwise in lock-step
        through overlapped refreshes — thread timing must not leak."""
        mk = lambda: _pipe(refresh_every=4, refresh_mode="delta",   # noqa: E731
                           refresh_async=True, refresh_lead=2,
                           drift_frac=0.2)
        a, b = mk(), mk()
        for _ in range(14):
            ba, bb = a.next_batch(), b.next_batch()
            np.testing.assert_array_equal(np.asarray(ba["example_ids"]),
                                          np.asarray(bb["example_ids"]))
        a.finalize(), b.finalize()

    def test_dirty_mask_tracks_visits_and_resets(self):
        from repro.data import LSHSampledPipeline
        pipe = LSHSampledPipeline(
            jax.random.PRNGKey(5), _tokens(n=64), feature_fn, query_fn,
            LSHPipelineConfig(k=4, l=8, minibatch=8, refresh_every=100,
                              refresh_mode="delta"),
            params=PARAMS)
        seen = set()
        for _ in range(3):
            seen |= set(np.asarray(pipe.next_batch()["example_ids"]).tolist())
        dirty = set(np.flatnonzero(np.asarray(pipe._dirty)).tolist())
        assert dirty == seen
        pipe.refresh(full=False)
        assert not np.any(np.asarray(pipe._dirty))

    def test_invalid_refresh_mode_rejected(self):
        with pytest.raises(ValueError):
            LSHPipelineConfig(refresh_mode="incremental")


class TestOverlappedRefresh:
    def test_async_refresh_bit_matches_sync(self):
        """The double-buffered host-thread refresh swaps at the same step
        boundary as the synchronous path -> identical batch sequences."""
        sync = _pipe(refresh_every=6, refresh_async=False)
        asyn = _pipe(refresh_every=6, refresh_async=True, refresh_lead=2)
        for _ in range(20):
            bs, ba = sync.next_batch(), asyn.next_batch()
            assert np.array_equal(np.asarray(bs["example_ids"]),
                                  np.asarray(ba["example_ids"]))
            np.testing.assert_allclose(
                np.asarray(bs["loss_weights"]),
                np.asarray(ba["loss_weights"]), rtol=1e-6)
        assert all(p._refresh_count >= 3 for p in asyn.shards)
        asyn.finalize()


class TestElasticReshard:
    def test_reshard_restore_is_bit_deterministic(self):
        """Restoring onto a CHANGED mesh shape (4 -> 2 shards) rebuilds
        per-shard indexes bit-identically across repeated restores."""
        tokens = _tokens(n=120, seed=5)
        cfg = LSHPipelineConfig(k=4, l=8, minibatch=16, refresh_every=6)

        def rebuild():
            return rebuild_sharded_pipeline(
                jax.random.PRNGKey(7), tokens, feature_fn, query_fn, cfg,
                step=13, n_shards=2, params=PARAMS)

        a, b = rebuild(), rebuild()
        assert len(a.shards) == 2
        for sa, sb in zip(a.shards, b.shards):
            assert sa._step == 13
            assert sa._refresh_count == (13 - 1) // 6
            np.testing.assert_array_equal(
                np.asarray(sa.index.sorted_codes),
                np.asarray(sb.index.sorted_codes))
            np.testing.assert_array_equal(np.asarray(sa.index.order),
                                          np.asarray(sb.index.order))
            np.testing.assert_array_equal(np.asarray(sa.index.projections),
                                          np.asarray(sb.index.projections))
        for _ in range(5):
            ba, bb = a.next_batch(), b.next_batch()
            np.testing.assert_array_equal(np.asarray(ba["example_ids"]),
                                          np.asarray(bb["example_ids"]))
            np.testing.assert_array_equal(np.asarray(ba["loss_weights"]),
                                          np.asarray(bb["loss_weights"]))

    def test_restored_step_continues_native_key_streams(self):
        """A pipeline restored at step t draws the same sample indices as
        one that ran to t without interruption (fold_in key streams),
        as long as no refresh re-embedded the features in between."""
        tokens = _tokens(n=80, seed=6)
        cfg = LSHPipelineConfig(k=4, l=8, minibatch=8, refresh_every=0)
        live = ShardedLSHPipeline(jax.random.PRNGKey(9), tokens, feature_fn,
                                  query_fn, cfg, n_shards=2, params=PARAMS)
        for _ in range(4):
            live.next_batch()
        restored = rebuild_sharded_pipeline(
            jax.random.PRNGKey(9), tokens, feature_fn, query_fn, cfg,
            step=4, n_shards=2, params=PARAMS)
        for _ in range(3):
            bl, br = live.next_batch(), restored.next_batch()
            np.testing.assert_array_equal(np.asarray(bl["example_ids"]),
                                          np.asarray(br["example_ids"]))


def _lm_cfg():
    return ModelConfig(
        name="tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, vocab=64, chunk=16, loss_chunk=16, dtype="float32",
        rope_theta=10000.0, lgd_enabled=True)


class TestTrainerSamplerHook:
    def test_end_to_end_sharded_lgd_training(self):
        cfg = _lm_cfg()
        corpus = make_token_corpus(11, 256, 16, cfg.vocab, hard_frac=0.15)
        params = init_params(KEY, cfg)
        sampler = ShardedLSHPipeline(
            jax.random.PRNGKey(12), corpus.tokens,
            mean_pool_feature_fn(cfg), lm_head_query_fn(),
            LSHPipelineConfig(k=5, l=10, minibatch=16, refresh_every=10,
                              refresh_async=True),
            n_shards=2, params=params)
        tr = Trainer(cfg, params, Adam(lr=1e-2),
                     tcfg=TrainerConfig(log_every=100), sampler=sampler)
        assert tr.tcfg.donate is False        # forced: sampler reads params
        out = tr.run(25)
        tr.finalize()
        assert all(np.isfinite(out["losses"]))
        assert np.mean(out["losses"][-5:]) < np.mean(out["losses"][:5])
        # the hook kept the sampler pointed at the live params
        assert sampler.params is tr.params

    def test_legacy_closure_pipeline_as_sampler(self):
        """A PR-1-era pipeline (closures, no params=) must survive the
        trainer's unconditional set_params calls: set_params only stores
        the value, it must not flip the hook calling convention."""
        from repro.data import LSHSampledPipeline
        cfg = _lm_cfg()
        with pytest.warns(DeprecationWarning, match="legacy closure"):
            pipe = LSHSampledPipeline(
                jax.random.PRNGKey(13), _tokens(n=64, seq=9),
                lambda chunk: jnp.mean(EMBED[chunk], axis=1),   # legacy
                lambda: jnp.ones((DIM,)),                        # legacy
                LSHPipelineConfig(k=4, l=8, minibatch=8,
                                  refresh_every=4))
        tr = Trainer(cfg, init_params(KEY, cfg), Adam(lr=1e-2),
                     tcfg=TrainerConfig(log_every=100), sampler=pipe)
        out = tr.run(6)                    # crosses a refresh boundary
        tr.finalize()
        assert all(np.isfinite(out["losses"]))

    def test_chunked_runs_match_single_run_batch_stream(self):
        """run(8)+run(8) must consume exactly the ticks a run(16)
        consumes — no thrown-away prefetch at chunk boundaries (the
        restore-at-step contract depends on batch k training step k)."""
        cfg = _lm_cfg()
        corpus = make_token_corpus(11, 128, 16, cfg.vocab)

        def make(seed_params):
            sampler = ShardedLSHPipeline(
                jax.random.PRNGKey(14), corpus.tokens,
                mean_pool_feature_fn(cfg), lm_head_query_fn(),
                LSHPipelineConfig(k=4, l=8, minibatch=8, refresh_every=0),
                n_shards=2, params=seed_params)
            return Trainer(cfg, seed_params, Adam(lr=1e-2),
                           tcfg=TrainerConfig(log_every=100),
                           sampler=sampler), sampler

        tr_a, samp_a = make(init_params(KEY, cfg))
        losses_a = tr_a.run(16)["losses"]
        tr_b, samp_b = make(init_params(KEY, cfg))
        losses_b = tr_b.run(8)["losses"] + tr_b.run(8)["losses"]
        np.testing.assert_allclose(losses_a, losses_b, rtol=1e-5)
        assert all(p._step == 16 for p in samp_a.shards)
        assert all(p._step == 16 for p in samp_b.shards)

    def test_exactly_one_batch_source(self):
        cfg = _lm_cfg()
        params = init_params(KEY, cfg)
        with pytest.raises(ValueError):
            Trainer(cfg, params, Adam(lr=1e-2))


class TestDPAllReduceComposition:
    def test_shard_map_mean_equals_host_composition(self):
        """On a forced 4-device host mesh, the DP all-reduce (pmean of
        per-device weighted means) over a ShardedLSHPipeline batch equals
        the host-side global weighted mean — the estimator the sharded
        weights were composed for.  Runs in a subprocess because device
        count must be fixed before jax initialises."""
        script = textwrap.dedent("""
            import jax, numpy as np, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            from repro.data import LSHPipelineConfig, ShardedLSHPipeline
            from repro.dist.sharding import batch_sharding

            assert jax.device_count() == 4, jax.device_count()
            VOCAB, DIM = 50, 16
            EMBED = jax.random.normal(jax.random.PRNGKey(1), (VOCAB, DIM))
            PARAMS = {"embed": EMBED, "q": jnp.ones((DIM,))}
            tokens = np.asarray(jax.random.randint(
                jax.random.PRNGKey(2), (96, 9), 0, VOCAB), np.int32)
            ffn = lambda p, c: jnp.mean(p["embed"][c], axis=1)
            qfn = lambda p: p["q"]
            mesh = jax.make_mesh((4, 1), ("data", "model"))
            pipe = ShardedLSHPipeline(
                jax.random.PRNGKey(7), tokens, ffn, qfn,
                LSHPipelineConfig(k=4, l=8, minibatch=16, refresh_every=0,
                                  normalize_weights=False),
                n_shards=4, params=PARAMS, mesh=mesh)
            b = pipe.next_batch()
            # per-example values on the host: indexing a replicated
            # table with a data-sharded index array needs a mesh context
            v = jnp.asarray(np.asarray(EMBED)[np.asarray(b["tokens"])]
                            .mean(axis=(1, 2)) + 2.0)
            host = float(jnp.mean(b["loss_weights"] * v))

            @jax.jit
            def dp_estimate(w, v):
                def per_device(w, v):
                    return jax.lax.pmean(jnp.mean(w * v), "data")
                return shard_map(per_device, mesh=mesh,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=P())(w, v)

            dist = float(dp_estimate(b["loss_weights"], v))
            assert abs(dist - host) < 1e-5 * max(1.0, abs(host)), (dist, host)
            print("OK", dist, host)
        """)
        _run_on_4_devices(script)

    def test_each_shard_lives_on_one_device(self):
        """With mesh-sharded params, every shard's store, features, index
        and query sit on ONE device of its DP group (per-shard programs,
        Pallas kernels among them, must not span the mesh), the four
        shards cover the four devices, and refreshes keep it so."""
        script = textwrap.dedent("""
            import jax, numpy as np, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.data import LSHPipelineConfig, ShardedLSHPipeline
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh()
            VOCAB, DIM = 50, 16
            PARAMS = jax.device_put(
                {"embed": jax.random.normal(jax.random.PRNGKey(1),
                                            (VOCAB, DIM)),
                 "q": jnp.ones((DIM,))},
                NamedSharding(mesh, P()))
            tokens = np.asarray(jax.random.randint(
                jax.random.PRNGKey(2), (96, 9), 0, VOCAB), np.int32)
            ffn = jax.jit(lambda p, c: jnp.mean(p["embed"][c], axis=1))
            pipe = ShardedLSHPipeline(
                jax.random.PRNGKey(7), tokens, ffn, lambda p: p["q"],
                LSHPipelineConfig(k=4, l=8, minibatch=16, refresh_every=1),
                n_shards=4, params=PARAMS, mesh=mesh)
            for _ in range(3):
                b = pipe.next_batch()
            homes = []
            for p in pipe.shards:
                sets = {frozenset(x.sharding.device_set) for x in (
                    p.store, p.features, p.index.sorted_codes,
                    p.index.order, p._query())}
                assert len(sets) == 1 and len(next(iter(sets))) == 1, sets
                homes.append(next(iter(sets)))
            assert set().union(*homes) == set(jax.devices()), homes
            assert b["tokens"].sharding.device_set == set(jax.devices())
            assert pipe.health_summary()["refreshes"] >= 1
            print("OK")
        """)
        _run_on_4_devices(script)


def _run_on_4_devices(script: str):
    """Run ``script`` in a subprocess on a forced 4-device CPU host (the
    device count must be fixed before jax initialises)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
