"""Chip check: LGD training at Granite-3-8B width through the normal path.

    python chip_smoke.py              # one TPU chip (the default)
    python chip_smoke.py --chips 4    # the four-chip data-parallel phase only

One chip: ``repro.launch.train.train`` builds the one-chip cut of
Granite-3-8B (``configs/granite_3_8b.py``: every published width, one
layer, seq 4096) with a ``ShardedLSHPipeline`` over a seeded corpus,
then takes five LGD steps with an index refresh every two.  It prints
build, compile and per-step seconds, the losses, peak HBM, the refresh
count, fallback rate and health, whether the sample program holds the
Pallas kernels, and how the kernels compare with their XLA references on
this run's own features, query and draw.

Four chips: the same function on a (4, 1) mesh with one LSH index per
data-parallel shard, each shard's store and index on its own device; the
first step's loss is recomputed from the same params and global batch on
one device, and the params, the shard stores and the batch must span all
four devices.

Runs in one process, never in interpret mode, and fails (non-zero exit,
no result line) unless JAX's first device is a TPU.  The last line of
stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
STEPS = 5               # LGD steps on one chip
REFRESH_EVERY = 2       # two refreshes complete within STEPS draws

# Kernel-vs-reference tolerances on the chip, and why:
# * codes: both sides project in f32 at Precision.HIGHEST, but the MXU
#   kernel and XLA's dot sum in different orders, so a bit differs only
#   where a projection lies within f32 rounding of zero — expected well
#   under 1e-4 of the codes; more than 1e-3 means a wrong kernel.
CODE_MISMATCH_MAX = 1e-3
# * (lo, hi): exact in every table where the query's own code agrees
#   (the counts are integer); a code may differ in at most one of L
#   tables for the reason above.
QUERY_TABLES_DIFFERING_MAX = 1
# * weights 1/(max(p, floor) N): the rows must be bitwise equal; the f32
#   division is not correctly rounded on TPU and the kernel and XLA may
#   refine the reciprocal differently — a few ulp, far below 1e-5.
WEIGHT_RTOL = 1e-5
# * four chips vs one: bf16 activations, and the two programs partition
#   the matmuls and reduce the token mean in different orders.
LOSS_RTOL = 2e-3


class Failed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise Failed(what)


def line(key, value):
    print(f"{key}: {value}", flush=True)


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


class CountWarnings(logging.Handler):
    """Counts WARNING+ records of the pipeline's health logger: every
    refresh or recovery failure it catches is logged there."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.records = []

    def emit(self, record):
        self.records.append(self.format(record))


def compare_kernels(shard, key):
    """Pallas kernels against their XLA references on the run's data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import probe_masks, sample
    from repro.kernels.bucket_probe import bucket_probe_multi
    from repro.kernels.gather_weight import gather_weight
    from repro.kernels.simhash import simhash_codes

    idx, lsh = shard.index, shard.lsh
    k, l, proj = lsh.k, lsh.l, idx.projections
    ck, cr = (simhash_codes(shard.features, proj, k=k, l=l, use_pallas=u)
              for u in (True, False))
    code_mismatch = float(jnp.mean((ck != cr).astype(jnp.float32)))
    line("codes_compared", f"{ck.shape[0]} rows x {l} tables")
    line("codes_mismatch_fraction", code_mismatch)
    check(code_mismatch <= CODE_MISMATCH_MAX,
          f"simhash kernel codes differ from the reference in "
          f"{code_mismatch} of codes (max {CODE_MISMATCH_MAX})")

    q = shard._query()
    qk, qr = (simhash_codes(q[None], proj, k=k, l=l, use_pallas=u)[0]
              for u in (True, False))
    agree = np.asarray(qk == qr)
    # the probe sequence the sample program walks (mask 0 = exact bucket)
    masks = probe_masks(k, 1 + shard.cfg.multiprobe)
    (lo_k, hi_k), (lo_r, hi_r) = (
        bucket_probe_multi(q, proj, idx.sorted_codes, masks, k=k, l=l,
                           use_pallas=u)
        for u in (True, False))                              # (J, L)
    same = np.asarray(jnp.all((lo_k == lo_r) & (hi_k == hi_r), axis=0))
    sizes = np.asarray(hi_k - lo_k)
    line("query_tables_differing", int((~agree).sum()))
    line("probe_codes_per_table", len(masks))
    line("lo_hi_equal_tables", f"{int(same.sum())}/{l}")
    line("exact_bucket_sizes", sizes[0].tolist())
    line("probed_rows_per_table", sizes.sum(axis=0).tolist())
    check(int((~agree).sum()) <= QUERY_TABLES_DIFFERING_MAX,
          f"query codes differ in {int((~agree).sum())} tables")
    check(bool(np.all(same | ~agree)),
          "bucket_probe (lo, hi) differ from the reference where the "
          "query codes agree")

    res = sample(key, idx, shard.features, q, lsh, m=64,
                 multiprobe=shard.cfg.multiprobe, use_pallas=True)
    (rows_k, w_k), (rows_r, w_r) = (
        gather_weight(shard.store, res.indices, res.probs,
                      p_floor=shard.cfg.p_floor, use_pallas=u)
        for u in (True, False))
    rows_equal = bool(jnp.all(rows_k == rows_r))
    w_err = float(jnp.max(jnp.abs(w_k - w_r) / jnp.abs(w_r)))
    line("gather_rows_equal", rows_equal)
    line("weights_max_rel_diff", w_err)
    check(rows_equal, "gather_weight rows differ from store[idx]")
    check(w_err <= WEIGHT_RTOL,
          f"gather_weight weights differ by {w_err} (rtol {WEIGHT_RTOL})")
    jax.block_until_ready(w_k)


def one_chip():
    import jax
    from repro.configs import granite_3_8b as granite
    from repro.core import sample_gather
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train

    cfg, shape = granite.ONE_CHIP, granite.ONE_CHIP_TRAIN
    mesh = make_host_mesh()
    line("config", f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model}"
         f" heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff}"
         f" vocab={cfg.vocab} seq={shape['seq']} batch={shape['batch']}"
         f" corpus={shape['corpus']} multiprobe={shape['multiprobe']}"
         f" refresh_every={REFRESH_EVERY}")
    t0 = time.perf_counter()
    tr = train(cfg, steps=0, lgd=True, refresh_every=REFRESH_EVERY,
               log_every=1, mesh=mesh, **shape)
    line("build_s", time.perf_counter() - t0)

    losses, step_s = [], []
    with use_mesh(mesh):
        for _ in range(STEPS):
            t = time.perf_counter()
            losses += tr.run(1)["losses"]
            jax.block_until_ready(tr.params)
            step_s.append(time.perf_counter() - t)
    # every other draw joins an in-flight index refresh; the fastest
    # later step is one that did not
    steady = min(step_s[1:])
    line("first_step_s", step_s[0])
    line("compile_s", step_s[0] - steady)
    line("step_s", step_s[1:])
    line("step_s_min", steady)
    line("losses", losses)
    line("peak_bytes_in_use", peak_bytes(jax.devices()[0]))
    check(len(losses) == STEPS and all(map(math.isfinite, losses)),
          f"losses not all finite: {losses}")

    sampler = tr.sampler
    shard = sampler.shards[0]
    key = jax.random.PRNGKey(SEED)
    args, kw = shard.draw_inputs(key, shard._query())
    hlo = sample_gather.lower(*args, **kw).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    line("sample_program_tpu_custom_calls", n_kernels)
    check(n_kernels > 0, "no Pallas kernel in the sample program")
    compare_kernels(shard, key)

    tr.finalize()
    hs = sampler.health_summary()
    st = sampler.sampler_stats()
    line("refreshes", hs["refreshes"])
    line("refresh_failures", hs["refresh_failures"])
    line("caught_errors", hs["caught_errors"])
    line("fallback_rate", st["fallback_rate"])
    line("health", hs["state"])
    check(hs["refreshes"] >= 1, "no refresh completed")
    check(hs["caught_errors"] == 0 and hs["refresh_failures"] == 0,
          f"the pipeline caught errors: {hs['last_error']}")
    check(hs["state"] == "healthy", f"health is {hs['state']}")
    return len(jax.devices())


def four_chips():
    import jax
    import numpy as np
    from repro.configs import granite_3_8b as granite
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train
    from repro.models import loss as lm_loss

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, "
          f"JAX sees {len(devices)}")
    cfg = granite.ONE_CHIP
    seq = granite.ONE_CHIP_TRAIN["seq"]
    mesh = make_host_mesh()
    line("config", f"{cfg.name} mesh={dict(mesh.shape)} seq={seq} "
         f"batch=4 n_shards=4")
    t0 = time.perf_counter()
    tr = train(cfg, steps=0, batch=4, seq=seq, corpus=256, lgd=True,
               refresh_every=0, mesh=mesh,
               multiprobe=granite.ONE_CHIP_TRAIN["multiprobe"])
    line("build_s", time.perf_counter() - t0)
    check(tr.sampler.n_shards == 4, f"n_shards={tr.sampler.n_shards}")

    seen = []
    draws = tr.batches

    def recorded():
        for b in draws:
            seen.append(b)
            yield b
    tr.batches = recorded()
    params0 = tr.params
    t = time.perf_counter()
    with use_mesh(mesh):
        loss4 = tr.run(1)["losses"][0]
    line("first_step_s", time.perf_counter() - t)
    batch = seen[0]

    all4 = set(devices)
    leaves = jax.tree.leaves(params0)
    check(all(set(x.sharding.device_set) == all4 for x in leaves),
          "a parameter does not span all four devices")
    split = sum(x.addressable_shards[0].data.size < x.size for x in leaves)
    line("param_leaves_split_over_devices", f"{split}/{len(leaves)}")
    check(split > 0, "every parameter is replicated: nothing is sharded")
    per_dev = {d: 0 for d in devices}
    for x in leaves:
        for s in x.addressable_shards:
            per_dev[s.device] += s.data.nbytes
    line("param_bytes_per_device", [per_dev[d] for d in devices])
    check(all(v > 0 for v in per_dev.values()),
          "a device holds no parameter bytes")
    # one LSH index per data-parallel shard, each on its own device
    homes = [p.store.sharding.device_set for p in tr.sampler.shards]
    line("shard_store_devices", [sorted(d.id for d in h) for h in homes])
    check(all(len(h) == 1 for h in homes) and set().union(*homes) == all4,
          "the shard stores are not one per device over all four devices")
    check(set(batch["tokens"].sharding.device_set) == all4,
          "the global batch does not span all four devices")

    one = devices[0]
    p1 = jax.device_put(params0, one)
    b1 = {k: jax.device_put(np.asarray(v), one) for k, v in batch.items()}
    loss1 = float(jax.jit(lambda p, b: lm_loss(p, cfg, b))(p1, b1))
    rel = abs(loss4 - loss1) / abs(loss1)
    line("loss_4_chips", loss4)
    line("loss_1_device", loss1)
    line("loss_rel_diff", rel)
    check(math.isfinite(loss4) and rel <= LOSS_RTOL,
          f"four-chip loss {loss4} vs one device {loss1} "
          f"(rtol {LOSS_RTOL})")
    tr.finalize()
    hs = tr.sampler.health_summary()
    check(hs["caught_errors"] == 0 and hs["state"] == "healthy",
          f"pipeline health {hs['state']}: {hs['last_error']}")
    line("peak_bytes_in_use", [peak_bytes(d) for d in devices])
    return len(devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"error: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"error: JAX's first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache
    line("compile_cache", enable_compile_cache())
    line("device", f"{dev.platform} {dev.device_kind} x{len(jax.devices())}")

    watch = CountWarnings()
    logging.getLogger("repro.lgd").addHandler(watch)
    try:
        count = four_chips() if args.chips == 4 else one_chip()
        check(not watch.records,
              f"the pipeline logged caught failures: {watch.records}")
    except Failed as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
