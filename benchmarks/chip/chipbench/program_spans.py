"""The program's own host spans in a traced run, on the device's clock.

The program opens profiler spans at the boundaries of its host work
(``repro.spans``): ``train`` (one step), ``train/dispatch``,
``train/sync`` and ``train/draw`` on the trainer's thread, ``lgd/...``
inside a draw, ``index/...`` around a refresh (``index/refresh``,
``index/embed`` and ``index/rehash`` on the refresh worker's thread,
``index/refresh_join`` on the trainer's).  This module reads them from the
run's profile beside the device planes and

* bounds the offset of the device's clock from the host's at each end of
  the window with the two ``bench_window_mark`` programs: a mark's device
  run starts after its host dispatch (``PjitFunction(bench_window_mark)``)
  starts, and ends before the host reaches the next span boundary on that
  thread (``bench/step`` opening, ``bench/window`` closing);
* maps every span onto the device's clock with the middle of the two
  ends' common bounds (one offset for the window), or where they share
  none, with each end's middle and linearly in between; and clips it to
  the window;
* totals each span name's calls, seconds and self seconds (less its
  nested spans on the same thread) inside the window;
* names each idle gap of the device by the innermost program span open on
  the trainer's thread, plus the innermost ``index/`` span open on any
  other thread; else by the innermost ``bench/`` span open; else
  ``host outside bench spans``.

Each host thread is a line of its own in the profile, and every Python
thread's line is named ``python``, so lines are kept apart by position,
never by name.

``window(run)`` reads the traced run's profile once, logs the offsets, the
span table, the longest gaps and the idle time by name on stderr, and
returns the reduction (None where the run was not traced or its program
opens no spans).  The per-layer metrics ``host_sync_ms``,
``draw_dispatch_ms`` and ``refresh_join_ms`` read its totals.
"""

from __future__ import annotations

import bisect
import collections
import math
import os
import sys
import traceback
from types import SimpleNamespace

from . import spec
from .trace_reduce import MARK, TOP, find_xplane, module_name, union

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")   # bench.py's
HOST_MARK = "PjitFunction(bench_window_mark)"
PROGRAM = ("train", "lgd/", "index/")
OUTSIDE = "host outside bench spans"


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


def _kept(name: str) -> bool:
    return is_program(name) or name.startswith("bench/") or name == HOST_MARK


def read_profile(path):
    """(host lines, device planes) of a ``.xplane.pb``: each host thread's
    kept spans as [(name, start_ns, end_ns)], and each TPU plane's
    ``XLA Modules`` and ``XLA Ops`` events as {line: [(name, start, dur)]}."""
    from jax.profiler import ProfileData
    host, devices = [], []
    for p in ProfileData.from_file(path).planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if _kept(e.name)]
                if evs:
                    host.append(evs)
        elif p.name.startswith("/device:TPU:"):
            devices.append({line.name: [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
                            for line in p.lines
                            if line.name in ("XLA Modules", "XLA Ops")})
    return host, devices


def _outermost(evs):
    """Events not nested in an earlier one of the list."""
    out = []
    for s, e in sorted(evs):
        if not out or s >= out[-1][1]:
            out.append((s, e))
    return out


def clock_offsets(host, dev_marks):
    """[(host time, lower, upper)] of (device - host) clock at each mark.

    ``dev_marks``: the marks' device runs [(start, end)], in order."""
    out = []
    for line in host:
        marks = _outermost([(s, e) for n, s, e in line if n == HOST_MARK])
        if not marks:
            continue
        edges = sorted(x for n, s, e in line if n != HOST_MARK
                       for x in (s, e))
        for (hs, he), (ds, de) in zip(marks, dev_marks):
            i = bisect.bisect_left(edges, he)
            lower = de - edges[i] if i < len(edges) else None
            out.append((hs, lower, ds - hs))
        break
    return out


def _estimate(lower, upper):
    return upper if lower is None or lower > upper else (lower + upper) / 2


def host_to_device(offsets):
    """A function mapping a host time onto the device's clock.

    The two clocks tick together over a window (both are the host's
    monotonic clock, one of them read through the device's timestamps), so
    where the ends' bounds overlap, one offset fits both: the middle of the
    overlap.  A wide bound at one end (the first mark can wait tens of ms
    for the device) then takes the other end's."""
    if not offsets:
        return lambda t: t
    lowers = [lo for _, lo, _ in offsets if lo is not None]
    lower = max(lowers) if lowers else None
    upper = min(up for _, _, up in offsets)
    if lower is None or lower <= upper:
        d = _estimate(lower, upper)
        return lambda t: t + d
    (h0, d0), (h1, d1) = [(h, _estimate(lo, up)) for h, lo, up in
                          (offsets[0], offsets[-1])]
    if h1 == h0:
        return lambda t: t + d0
    return lambda t: t + d0 + (d1 - d0) * (t - h0) / (h1 - h0)


def innermost(spans):
    """Sorted [(start, end, name)] segments, each labelled by the innermost
    of the (nested) ``spans`` [(name, start, end)] open over it."""
    out, stack, t = [], [], -math.inf

    def advance(to):
        nonlocal t
        while stack and stack[-1][0] <= to:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if stack and to > t:
            out.append((t, to, stack[-1][1]))
        t = max(t, to)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        advance(s)
        stack.append((e, name))
    advance(math.inf)
    return out


class Timeline:
    """Which name is innermost at a time, from ``innermost`` segments."""

    def __init__(self, segments):
        self.segments = segments
        self.starts = [s for s, e, n in segments]

    def at(self, t):
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.segments[i][1]:
            return self.segments[i][2]
        return None

    def edges(self):
        return sorted({x for s, e, n in self.segments for x in (s, e)})


def _self_seconds(spans, lo, hi):
    """{name: [calls, seconds, self seconds]} of one thread's nested spans,
    clipped to [lo, hi)."""
    out = collections.defaultdict(lambda: [0, 0.0, 0.0])
    stack = []   # [end, name, clipped duration, children's clipped sum]

    def close(entry):
        end, name, dur, kids = entry
        row = out[name]
        row[0] += 1
        row[1] += dur
        row[2] += dur - kids
        if stack:
            stack[-1][3] += dur

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        dur = max(0, min(e, hi) - max(s, lo))
        if e > lo and s < hi:
            stack.append([e, name, dur, 0])
    while stack:
        close(stack.pop())
    return {k: (v[0], v[1] / 1e9, v[2] / 1e9) for k, v in out.items()}


def reduce(host, devices):
    """The program spans of one traced window; None where there are none.

    ``host``: one list of [(name, start_ns, end_ns)] per host thread;
    ``devices``: per TPU plane, {"XLA Modules": [...], "XLA Ops": [...]}
    of (name, start_ns, dur_ns)."""
    if not any(is_program(n) for line in host for n, s, e in line):
        return None
    marks = sorted((s, s + d) for lines in devices
                   for n, s, d in lines.get("XLA Modules", [])
                   if module_name(n) == MARK)
    if len(marks) < 2:
        raise ValueError(f"the trace holds fewer than two {MARK} programs")
    lo, hi = marks[0][1], marks[-1][0]
    offsets = clock_offsets(host, [marks[0], marks[-1]])
    to_dev = host_to_device(offsets)
    lines = [[(n, to_dev(s), to_dev(e)) for n, s, e in line if n != HOST_MARK]
             for line in host]

    def trainer_likeness(line):
        names = [n for n, s, e in line]
        return (names.count("train"), names.count("bench/step"),
                sum(map(is_program, names)))
    main = max(range(len(lines)), key=lambda i: trainer_likeness(lines[i]))

    totals = {}
    for line in lines:
        for name, row in _self_seconds(
                [x for x in line if is_program(x[0])], lo, hi).items():
            c, t, st = totals.get(name, (0, 0.0, 0.0))
            totals[name] = (c + row[0], t + row[1], st + row[2])

    program = Timeline(innermost(
        [x for x in lines[main] if is_program(x[0])]))
    bench = Timeline(innermost(
        [x for line in lines for x in line if x[0].startswith("bench/")]))
    others = [Timeline(innermost(
        [x for x in line if x[0].startswith("index/")]))
        for i, line in enumerate(lines) if i != main]

    def label(t):
        first = program.at(t) or bench.at(t)
        parts = [first] + [o.at(t) for o in others]
        return "+".join(p for p in parts if p) or OUTSIDE

    edges = sorted(set(program.edges()).union(
        *(o.edges() for o in others), bench.edges()))
    gaps, idle = [], collections.Counter()
    for lines_d in devices:
        merged = union([(max(s, lo), min(s + d, hi))
                        for n, s, d in lines_d.get("XLA Ops", [])
                        if s + d > lo and s < hi])
        bounds = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(bounds[::2], bounds[1::2]):
            if b <= a:
                continue
            gaps.append((b - a, a, b))
            cuts = [a] + edges[bisect.bisect_right(edges, a):
                               bisect.bisect_left(edges, b)] + [b]
            for x, y in zip(cuts, cuts[1:]):
                idle[label((x + y) / 2)] += y - x
    n_dev = max(len(devices), 1)
    top = sorted(gaps, reverse=True)[:TOP]
    return SimpleNamespace(
        window_s=(hi - lo) / 1e9,
        offsets=[(h, lo_, up, to_dev(h) - h) for h, lo_, up in offsets],
        totals=totals,
        gaps=[(label((a + b) / 2), d / 1e9, (a - lo) / 1e9)
              for d, a, b in top],
        idle_s={k: v / n_dev / 1e9 for k, v in idle.most_common()})


def seconds(r, name):
    """Seconds of ``name`` inside the window (0 where it never ran)."""
    return r.totals.get(name, (0, 0.0, 0.0))[1]


def log_summary(r, run, out=sys.stderr):
    def p(*a):
        print(*a, file=out)
    for end, (h, lower, upper, used) in zip(("start", "end"), r.offsets):
        lo_ms = "none" if lower is None else f"{lower / 1e6:.6f}"
        p(f"clock offset device-host at window {end}: bounds {lo_ms} to "
          f"{upper / 1e6:.6f} ms, applied {used / 1e6:.6f} ms")
    p(f"program spans in the {r.window_s:.6f} s window "
      f"(name calls total_s self_s):")
    for name, (c, t, st) in sorted(r.totals.items()):
        p(f"  {name} {c} {t:.6f} {st:.6f}")
    draw = seconds(r, "train/draw")
    p(f"train/draw in window {draw:.6f} s, Trainer.data_seconds over the "
      f"window {run.host_draw_s:.6f} s")
    p("longest idle gaps (name ms at_s):")
    for name, d, at in r.gaps:
        p(f"  {name} {d * 1e3:.6f} {at:.6f}")
    idle = sum(r.idle_s.values())
    p(f"idle {idle:.6f} s of the window by name (name s share_of_idle):")
    for name, s in r.idle_s.items():
        p(f"  {name} {s:.6f} {s / idle if idle else 0.0:.4f}")


def window(run):
    """The reduction of ``run``'s traced window, read once per run; None
    where the run was not traced, its program opens no spans, or the
    profile cannot be read (logged)."""
    if getattr(run, "trace", None) is None:
        return None
    if not hasattr(run, "program_spans"):
        run.program_spans = None
        try:
            r = reduce(*read_profile(find_xplane(TRACE_DIR)))
        except Exception:                 # noqa: BLE001 — a metric reader
            traceback.print_exc()         # must not fail the run
            print("program spans: the profile could not be reduced",
                  file=sys.stderr)
        else:
            if r is None:
                print("program spans: none in the profile", file=sys.stderr)
            else:
                log_summary(r, run)
            run.program_spans = r
    return run.program_spans
