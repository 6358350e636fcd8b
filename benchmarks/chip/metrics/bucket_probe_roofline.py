"""The fused bucket-probe kernel's share of its roofline: the least time
the chip's peaks allow for hashing one query and counting the sorted codes
of every table under each probe code, over the kernel's device time."""

import sys

from chipbench import flops

KERNELS = ("bucket_probe_multi", "bucket_probe")


def read(run):
    t = run.trace
    sec = sum(t.op_s.get(k, 0.0) for k in KERNELS)
    calls = sum(t.op_calls.get(k, 0) for k in KERNELS)
    if not sec:
        return None
    c, tr = run.cfg, run.traffic
    ops, nbytes = flops.bucket_probe(1, c.d_model, tr["k"], tr["l"],
                                     tr["corpus_rows"] // run.chips,
                                     1 + tr["multiprobe"])
    share, bound = flops.roofline_share(
        ops * calls, nbytes * calls, sec, flops.peaks(run.device_kind))
    print(f"bucket_probe_roofline: {calls} calls, {bound} bound",
          file=sys.stderr)
    return share
