"""One run of one cell: set-up, warm-up with the readings ``correct`` needs,
the measured window, then the reference.

Set-up builds the trainer from the seed and drives that same object through
its first three steps one call at a time, recording each batch, each draw,
Adam's first moment after step one and the change of the parameters after
step three.  A cell that refreshes its index warms up through one whole
refresh period, so the window starts on a period boundary with every
program compiled.  The window then calls ``Trainer.run`` in chunks of
whole periods (or of about ``CHUNK_SECONDS`` of steps) until ``seconds``
have passed, with no device sync inside it, and ends on the parameters'
``block_until_ready``.  The reference runs once the window has closed,
the peak memory has been read and the program's state is freed.
"""

from __future__ import annotations

import gc
import math
import statistics
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import sample
from repro.dist.sharding import tree_param_shardings, use_mesh
from repro.launch.mesh import make_host_mesh

from . import check, reference, spec
from .build import build_trainer
from .traffic import PARAMS, PIPELINE, corpus_for, params_on, stream

CHUNK_SECONDS = 2.0     # length of one Trainer.run call in a cell without
#                         refreshes; a cell with refreshes runs whole periods
FEATURE_ROWS = 8        # refreshed rows the reference re-embeds
WARM_STEPS = 3          # steps the reference follows
ADAM_B1 = 0.9


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class StepClock:
    """The trainer's step hook: the host time at the end of every step."""

    def __init__(self):
        self.times = []

    def __call__(self, trainer):
        self.times.append(time.perf_counter())


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


@jax.jit
def bench_window_mark(x):
    """A tiny program run at the window's start and end: its device events
    bound the traced window on the device's own clock."""
    return x + 1


def _host(x):
    return np.asarray(jax.device_get(x))


def warm_up(tr, traffic, seed):
    """Drive the first steps; returns the program's readings and the chunk."""
    lgd = traffic["sampler"] == "lgd"
    shard = tr.sampler.shards[0] if lgd else None
    feed, seen = tr.batches, []

    def recorded():
        for b in feed:
            seen.append(b)
            yield b
    tr.batches = recorded()
    out = {"losses": [], "draws": []}
    step_s = []
    for k in range(WARM_STEPS):
        if lgd:
            key, query = (jax.random.fold_in(shard._step_stream, shard._step),
                          shard._query())
        t = time.perf_counter()
        out["losses"] += tr.run(1)["losses"]
        step_s.append(time.perf_counter() - t)
        if k == 0:
            out["grad_norms"] = np.asarray(
                _host(_leaf_norms(tr.opt_state.m))) / (1 - ADAM_B1)
        if lgd:
            args, kw = shard.draw_inputs(key, query)
            res = sample(*args[:4], args[5], m=kw["m"],
                         multiprobe=kw["multiprobe"],
                         use_pallas=kw["use_pallas"],
                         interpret=kw["interpret"], n_live=kw["n_live"])
            out["draws"].append({**{f: _host(getattr(res, f)) for f in (
                "indices", "n_probes", "probe_code", "bucket_sizes",
                "fallback")}, "query": _host(query)})
    tr.batches = feed
    keys = ("tokens", "targets", "example_ids") + (
        ("loss_weights",) if lgd else ())
    out["batches"] = [{k: _host(b[k]) for k in keys} for b in seen]
    out["params"] = jax.device_get(tr.params)     # compared after the window
    if lgd:
        out["draw_state"] = {
            "features": _host(shard.features),
            "projections": _host(shard.index.projections),
            "k": shard.lsh.k, "multiprobe": shard.cfg.multiprobe,
            "p_floor": shard.cfg.p_floor}

    period = traffic.get("refresh_every", 0) if lgd else 0
    if period:
        grabbed = {}

        def grabbing():                  # the params the refresh embeds with
            for b in feed:
                snap = shard._refresh_snapshot
                if snap is not None and "params" not in grabbed:
                    grabbed["params"] = jax.device_get(snap[3])
                yield b
        tr.batches = grabbing()
        tr.run(period + 1 - WARM_STEPS)       # the swap lands on draw `period`
        tr.batches = feed
        rows = np.sort(np.random.default_rng(seed).choice(
            shard.n, FEATURE_ROWS, replace=False))
        out["refresh"] = {
            "params": grabbed["params"], "rows": rows,
            "row_features": _host(shard.features[rows]),
            "features": _host(shard.features),
            "projections": _host(shard.index.projections),
            "sorted_codes": _host(shard.index.sorted_codes),
            "order": _host(shard.index.order), "k": shard.lsh.k}
        chunk = period
    else:
        step = min(step_s[1:])
        chunk = min(64, max(8, math.ceil(CHUNK_SECONDS / step)))
        tr.run(chunk)
    jax.block_until_ready(tr.params)
    return out, chunk


def measure(tr, chunk, seconds, clock):
    """The window: whole chunks until ``seconds`` have passed."""
    feed = tr.batches

    def spanned():
        while True:
            with TraceAnnotation("bench/draw"):
                b = next(feed)
            yield b
    tr.batches = spanned()
    draw0 = tr.data_seconds
    clock.times = []
    steps = 0
    bench_window_mark(jnp.zeros(())).block_until_ready()
    t0 = time.perf_counter()
    while True:
        with TraceAnnotation("bench/step"):
            tr.run(chunk)
        steps += chunk
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(tr.params)
    t1 = time.perf_counter()
    bench_window_mark(jnp.zeros(())).block_until_ready()
    tr.batches = feed
    bounds = [t0] + clock.times
    return SimpleNamespace(
        t0=t0, t1=t1, window_s=t1 - t0, steps=steps,
        step_s=[b - a for a, b in zip(bounds, bounds[1:])],
        host_draw_s=tr.data_seconds - draw0)


def _opt(traffic):
    return {"lr": traffic["lr"], "warmup": traffic["warmup_steps"],
            "total": traffic["schedule_steps"], "clip": 1.0}


def reference_numbers(cell, cfg, seed, prog, control=None, detail=None):
    """The numbers ``correct`` compares, from the program's readings.

    With ``control`` (a lower precision than the configuration's), the
    reference at that precision takes the program's place on the program's
    own batches and draws, and the plain recomputations of the draw and the
    refresh run on bfloat16-rounded inputs: the readings that must fail.
    ``detail``, a dict, receives the raw readings behind the training gaps.
    """
    traffic = cell.traffic
    corpus = _host(corpus_for(seed, cfg, traffic)[0])
    # the reference's batches: the corpus rows at the drawn ids, weighted by
    # the plain 1/(p N) of each draw
    batches = []
    for i, b in enumerate(prog["batches"]):
        rows = corpus[b["example_ids"]]
        batches.append({"tokens": rows[:, :-1], "targets": rows[:, 1:]})
        if "draw_state" in prog:
            batches[-1]["loss_weights"] = check.plain_weights(
                prog["draws"][i], prog["draw_state"]).astype(np.float32)
    start = cell.arch.make_params(stream(seed, PARAMS), cfg)
    prog = {**prog, "change_norms": np.asarray([
        float(n) for n in _change_norms(prog["params"], start)])}
    ref = dict(zip(("losses", "grad_norms", "change_norms"),
                   reference.train_steps(cell.arch, start, batches, cfg,
                                         _opt(traffic))))
    got = prog
    if control:
        got = dict(zip(("losses", "grad_norms", "change_norms"),
                       reference.train_steps(cell.arch, start, batches,
                                             cfg, _opt(traffic), control)))
    del start
    gc.collect()
    nums = check.training_gaps(got, ref)
    if detail is not None:
        detail.update({f"{side}_{k}": [float(x) for x in d[k]]
                       for side, d in (("got", got), ("ref", ref))
                       for k in ("losses", "grad_norms", "change_norms")})
    nums["rows_mismatch"] = check.rows_mismatch(prog["batches"], corpus)
    lower = "bfloat16" if control else None
    if "draw_state" in prog:
        st = prog["draw_state"]
        weights = [b["loss_weights"] for b in prog["batches"]]
        if control:
            weights = [check.plain_weights(d, st, lower) for d in prog["draws"]]
        nums["weight_gap"] = check.weight_gap(weights, prog["draws"], st)
        nums["caught_errors"] = prog["caught_errors"]
    if "refresh" in prog:
        r = prog["refresh"]
        stored = check.codes_by_row(r["sorted_codes"], r["order"])
        if control:
            stored = check.plain_codes(r["features"], r["projections"],
                                       r["k"], lower)
        nums["code_mismatch"] = check.code_mismatch(stored, r)
        tokens = corpus[r["rows"], :-1]
        rows = r["row_features"]
        if control:
            rows = pooled_rows(cell.arch, r["params"], tokens, cfg, control)
            rows = rows / np.linalg.norm(rows, axis=1, keepdims=True)
        ref_rows = pooled_rows(cell.arch, r["params"], tokens, cfg, "float32")
        nums["feature_gap"] = check.feature_gap(rows, ref_rows)
        if detail is not None:
            detail["feature_row_gaps"] = check.feature_row_gaps(rows, ref_rows)
    return nums


def pooled_rows(arch, params_host, tokens, cfg, precision):
    """The reference's feature of each row, one row per call."""
    p = jax.tree.map(jnp.asarray, params_host)
    c = arch.constants(cfg)
    return np.stack([_host(reference.pooled_jit(
        arch, p, jnp.asarray(t[None]), c, precision)[0]) for t in tokens])


def set_up(cell, seed, mesh, clock=None):
    """The trainer of ``cell`` from ``seed``, driven through its warm-up.

    Returns (trainer, the program's readings, chunk size)."""
    cfg = spec.model_config(cell.config, cell.config_name)
    traffic = cell.traffic
    tokens, hard = corpus_for(seed, cfg, traffic)
    shapes = jax.eval_shape(lambda k: cell.arch.make_params(k, cfg),
                            stream(seed, PARAMS))
    shardings = tree_param_shardings(shapes, mesh)
    tr = build_trainer(
        cfg, traffic, tokens, hard,
        params_on(cell.arch, stream(seed, PARAMS), cfg, shardings), mesh,
        pipeline_key=stream(seed, PIPELINE), uniform_seed=seed,
        step_hook=clock)
    del tokens, hard
    prog, chunk = warm_up(tr, traffic, seed)
    return tr, prog, chunk


def close(tr, prog):
    """Join the pipeline, note what it caught, and let go of the trainer."""
    tr.finalize()
    if tr.sampler is not None:
        hs = tr.sampler.health_summary()
        st = tr.sampler.sampler_stats()
        log(f"sampler: state={hs['state']} refreshes={hs['refreshes']} "
            f"refresh_failures={hs['refresh_failures']} "
            f"caught_errors={hs['caught_errors']} "
            f"fallback_rate={st['fallback_rate']} "
            f"primary_miss_rate={st['primary_miss_rate']}")
        prog["caught_errors"] = hs["caught_errors"] + hs["refresh_failures"]
    tr.batches = tr._sampler = None
    gc.collect()


def run_cell(cell, seed, seconds, trace, t_start, trace_dir=None):
    """One run; returns the result line's fields.  A traced run profiles its
    window into ``trace_dir``."""
    from . import metrics_io, trace_reduce
    cfg = spec.model_config(cell.config, cell.config_name)
    traffic = cell.traffic
    mesh = make_host_mesh()
    dev = jax.devices()[0]
    with use_mesh(mesh):
        clock = StepClock()
        tr, prog, chunk = set_up(cell, seed, mesh, clock)
        setup_s = time.perf_counter() - t_start
        log(f"setup_s: {setup_s}  chunk: {chunk} steps")
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with TraceAnnotation("bench/window"):
            win = measure(tr, chunk, seconds, clock)
        if trace:
            jax.profiler.stop_trace()
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        close(tr, prog)
        del tr
        gc.collect()
    batch, seq = traffic["batch"], traffic["seq"]
    record = SimpleNamespace(
        cell=cell, cfg=cfg, traffic=traffic, chips=cell.chips,
        device_kind=dev.device_kind, setup_s=setup_s, peak_bytes=peak,
        window_s=win.window_s, steps=win.steps,
        tokens=win.steps * batch * seq, step_s=win.step_s,
        host_draw_s=win.host_draw_s, trace=None)
    if trace:
        record.trace = trace_reduce.reduce_dir(trace_dir)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = metrics_io.read_all(wanted, record)
    with use_mesh(mesh):
        nums = reference_numbers(cell, cfg, seed, prog)
    correct, rows = check.judge(nums, cell.limits)
    out = {
        "correct": correct, "attempted": win.steps,
        "failed": 0 if correct else win.steps, "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak}}
    if trace:
        out["device"]["busy_s"] = record.trace.busy_s
        out["device"]["window_s"] = record.trace.window_s
        out["breakdown"] = record.trace.breakdown
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    log(f"steps: {win.steps}  window_s: {win.window_s}  "
        f"step_s median: {statistics.median(win.step_s)}")
    for k, v, lim in rows:
        log(f"compared {k}: {v} limit {lim}")
    return out
