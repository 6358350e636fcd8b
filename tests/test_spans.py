"""Program spans and counters (``repro.spans``): the trainer's and the LGD
pipeline's host intervals, counted in memory and written to the profiler's
trace with their ``step=`` / ``refresh=`` metadata."""

import glob
import math
import sys
import threading

import jax
import pytest

from repro.data import (
    LSHPipelineConfig,
    ShardedLSHPipeline,
    lm_head_query_fn,
    make_token_corpus,
    mean_pool_feature_fn,
)
from repro.models import ModelConfig, init_params
from repro.optim import Adam
from repro.spans import Spans, merged
from repro.train import Trainer, TrainerConfig

ROWS, FEATURE_BATCH = 48, 20       # three embed chunks per full pass
REFRESH_EVERY = 4
STEPS = 9                          # draws 0..8: swaps at draws 4 and 8
EMBED_CALLS = math.ceil(ROWS / FEATURE_BATCH)


def _trainer():
    cfg = ModelConfig(
        name="tiny", n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, vocab=64, chunk=16, loss_chunk=16, dtype="float32",
        rope_theta=10000.0, lgd_enabled=True)
    corpus = make_token_corpus(11, ROWS, 16, cfg.vocab, hard_frac=0.15)
    params = init_params(jax.random.PRNGKey(0), cfg)
    sampler = ShardedLSHPipeline(
        jax.random.PRNGKey(12), corpus.tokens, mean_pool_feature_fn(cfg),
        lm_head_query_fn(),
        LSHPipelineConfig(k=5, l=10, minibatch=8,
                          refresh_every=REFRESH_EVERY, refresh_async=True,
                          refresh_lead=1),
        n_shards=1, feature_batch=FEATURE_BATCH, params=params)
    return Trainer(cfg, params, Adam(lr=1e-2),
                   tcfg=TrainerConfig(log_every=100), sampler=sampler)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A smoke-size LGD run over a refreshing pipeline, profiled."""
    tr = _trainer()
    built = tr.span_totals()
    out = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        tr.run(STEPS)
    finally:
        jax.profiler.stop_trace()
    tr.finalize()
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    return tr, built, path


def test_counters_of_a_refreshing_run(traced):
    tr, built, _ = traced
    tot = tr.span_totals()
    assert tot["train/draw"][0] == tr.data_seconds
    assert tot["train/draw"][1] == STEPS
    assert tot["train/dispatch"][1] == STEPS
    assert tot["train/sync"][1] == 2 * STEPS     # bool(ok) and float(loss)
    assert tot["lgd/draw"][1] == tot["lgd/query"][1] == STEPS
    assert tot["lgd/sample"][1] == tot["lgd/compose"][1] == STEPS
    swaps = tr.sampler.health_summary()["refreshes"]
    assert swaps == 2
    assert tot["index/refresh_join"][1] == swaps
    assert tot["index/refresh"][1] == swaps
    assert tot["index/rehash"][1] == swaps
    # the build embeds the shard once, each full refresh once more
    assert built["index/embed"][1] == EMBED_CALLS
    assert tot["index/embed"][1] - built["index/embed"][1] == \
        swaps * EMBED_CALLS
    # nested intervals: the draw holds the join, the trainer's the draw's
    assert tot["train/draw"][0] >= tot["lgd/draw"][0] >= \
        tot["index/refresh_join"][0]
    # host numbers only: reading them never waits on the device
    assert all(type(s) is float and type(n) is int
               for s, n in tot.values())


def _host_events(path):
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(dict(e.stats))
    return out


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_trace_holds_the_spans_with_their_ids(traced):
    _, _, path = traced
    ev = _host_events(path)
    assert sorted(s["step_num"] for s in ev["train"]) == list(range(STEPS))
    for name in ("train/dispatch", "lgd/draw", "lgd/sample"):
        assert sorted(s["step"] for s in ev[name]) == list(range(STEPS)), \
            name
    # one join and one worker refresh per swap, sharing the refresh id
    joins = sorted(s["refresh"] for s in ev["index/refresh_join"])
    assert joins == sorted(s["refresh"] for s in ev["index/refresh"]) \
        == [0, 1]
    # each refresh is launched one draw before its swap
    assert sorted(s["step"] for s in ev["index/refresh"]) == [3, 7]
    assert sorted(s["step"] for s in ev["index/refresh_join"]) == [4, 8]
    assert len(ev["index/embed"]) == 2 * EMBED_CALLS


def test_counts_survive_threads():
    """Several threads entering spans of one name lose no call."""
    spans, n_threads, n_calls = Spans(), 8, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_calls):
                with spans("index/embed"):
                    pass
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert spans.totals()["index/embed"][1] == n_threads * n_calls


def test_merged_sums_seconds_and_calls():
    assert merged({"a": (1.0, 2)}, {"a": (0.5, 1), "b": (2.0, 3)}) == {
        "a": (1.5, 3), "b": (2.0, 3)}


def test_a_raising_body_is_counted_and_propagates():
    spans = Spans()
    with pytest.raises(StopIteration):
        with spans("train/draw"):
            next(iter(()))
    assert spans.totals()["train/draw"][1] == 1
