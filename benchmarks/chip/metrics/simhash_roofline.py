"""The simhash kernel's share of its roofline: the least time the chip's
peaks allow for hashing the refreshed corpus shard, over the kernel's
device time."""

import sys

from chipbench import flops


def read(run):
    t = run.trace
    sec, calls = t.op_s.get("simhash_codes"), t.op_calls.get("simhash_codes")
    if not sec:
        return None
    c, tr = run.cfg, run.traffic
    ops, nbytes = flops.simhash(tr["corpus_rows"], c.d_model, tr["k"], tr["l"])
    share, bound = flops.roofline_share(
        ops * calls, nbytes * calls, sec, flops.peaks(run.device_kind))
    print(f"simhash_roofline: {calls} calls, {bound} bound", file=sys.stderr)
    return share
