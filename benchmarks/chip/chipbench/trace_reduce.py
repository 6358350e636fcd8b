"""From a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) with an ``XLA
Modules`` line (one event per program run, named ``jit_<fn>(<hash>)``) and
an ``XLA Ops`` line (one event per HLO instruction, named by its HLO text,
``%<name>.<n> = ...``; a Pallas kernel's instruction is named after its
``pallas_call``).  Host planes hold the benchmark's spans
(``bench/window``, ``bench/step``, ``bench/draw``, ``bench/refresh_embed``).
All events share one clock, host and device agreeing to about a
millisecond.

The window runs from the end of the first ``jit_bench_window_mark``
program to the start of the last, on the device's clock (the host span
``bench/window`` where the marks are missing).  The reduction keeps,
inside it: the union of each
chip's op intervals (busy time), device time by program and by HLO
instruction name (control-flow instructions, whose events span their
bodies' ops, are left out of the latter), and the longest idle gaps, each
named by the benchmark spans open on the host while the device waited.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from types import SimpleNamespace

WINDOW = "bench/window"
MARK = "jit_bench_window_mark"
# control-flow instructions whose events span the ops of their bodies
CONTAINERS = ("while", "conditional", "call")
_OP_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
TOP = 10


def op_name(hlo_text: str) -> str:
    """``%bucket_probe_multi.1 = (...) custom-call(...)`` -> ``bucket_probe_multi``."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def module_name(event_name: str) -> str:
    """``jit_train_step(5002186000)`` -> ``jit_train_step``."""
    return event_name.split("(", 1)[0]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_planes(planes):
    """``planes``: [(name, {line name: [(event name, start_ns, dur_ns)]})]."""
    host_spans = []
    devices = []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            devices.append(lines)
        elif pname.startswith("/host:"):
            for evs in lines.values():
                host_spans += [(n, s, s + d) for n, s, d in evs
                               if n.startswith("bench/")]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    marks = sorted((s, s + d) for lines in devices
                   for n, s, d in lines.get("XLA Modules", [])
                   if module_name(n) == MARK)
    wins = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if len(marks) >= 2:
        lo, hi = marks[0][1], marks[-1][0]
    elif wins:
        lo, hi = wins[0]
    else:
        raise ValueError(f"the trace holds neither {MARK} marks nor a "
                         f"{WINDOW!r} span")
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW]

    modules = collections.Counter()
    ops = collections.Counter()
    op_calls = collections.Counter()
    op_modules = collections.Counter()
    busy, gaps = [], []
    for lines in devices:
        mods = sorted((s, s + d, module_name(n))
                      for n, s, d in lines.get("XLA Modules", []))
        for s, e, n in mods:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                modules[n] += e - s
        starts = [m[0] for m in mods]
        ivals = []
        for n, s, d in lines.get("XLA Ops", []):
            s, e = _clip(s, s + d, lo, hi)
            if e <= s:
                continue
            ivals.append((s, e))
            name = op_name(n)
            if name in CONTAINERS:
                continue
            ops[name] += e - s
            op_calls[name] += 1
            i = _bisect(starts, s)
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            op_modules[f"{mod}/{n.split(' =', 1)[0].lstrip('%')}"] += e - s
        merged = union(ivals)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    gaps = sorted(gaps, reverse=True)[:TOP]
    return SimpleNamespace(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / n_dev / 1e9,
        module_s={k: v / n_dev / 1e9 for k, v in modules.items()},
        op_s={k: v / n_dev / 1e9 for k, v in ops.items()},
        op_calls={k: v / n_dev for k, v in op_calls.items()},
        n_devices=n_dev,
        breakdown={
            "device_ops": [[k, v / n_dev / 1e9]
                           for k, v in op_modules.most_common(TOP)],
            "idle_gaps": [[_open_spans(spans, (s + e) / 2), d / 1e9]
                          for d, s, e in gaps]})


def _bisect(starts, x):
    return bisect.bisect_right(starts, x) - 1


def _open_spans(spans, t):
    names = sorted({n for n, s, e in spans if s <= t < e})
    return "+".join(names) if names else "host outside bench spans"


def read_planes(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for p in pd.planes:
        if not (p.name.startswith("/device:TPU:") or p.name.startswith("/host:")):
            continue
        keep = {}
        for line in p.lines:
            if p.name.startswith("/device:") and line.name not in (
                    "XLA Modules", "XLA Ops"):
                continue
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            if p.name.startswith("/host:"):
                evs = [x for x in evs if x[0].startswith("bench/")]
            keep[line.name] = evs
        out.append((p.name, keep))
    return out


def find_xplane(prof_dir):
    files = sorted(glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {prof_dir}")
    return files[-1]


def reduce_dir(prof_dir):
    return reduce_planes(read_planes(find_xplane(prof_dir)))
