"""Operations and bytes from shapes, and the chip's peaks.

Kernel counts are what the algorithm needs for the call at its logical
shapes, not what a padded layout moves.  A model's FLOPs per training step
are its architecture module's (``chipbench/arch/<model_type>.py``).
"""

from __future__ import annotations

import json
import os

from .spec import HERE

PEAKS_FILE = os.path.join(HERE, "peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.load(open(PEAKS_FILE))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]


def simhash(n: int, d: int, k: int, l: int):
    """(ops, bytes) to hash n f32 rows of width d into l packed k-bit codes."""
    return 2.0 * n * d * k * l, 4.0 * (n * d + d * k * l + n * l)


def bucket_probe(b: int, d: int, k: int, l: int, n: int, j: int):
    """(ops, bytes) to hash b queries and count, for j probe codes in each of
    l tables, the sorted codes of n rows below and up to each code."""
    ops = 2.0 * b * d * k * l + 2.0 * j * b * l * n
    return ops, 4.0 * (b * d + d * k * l + l * n + 2 * j * b * l)


def roofline_share(ops, nbytes, seconds, peak):
    """(share in %, bound): least time at the peaks over the time taken."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
