"""Device time of the ops under a ``jax.named_scope`` in a traced run.

Each ``XLA Ops`` event of a TPU trace carries, in its metadata's ``tf_op``
stat, the scope path of the op that XLA kept for it, such as
``jit(train_step)/transpose(jvp(checkpoint))/mamba2/ssd/dot_general``
(a fusion carries its root op's).  ``jax.profiler.ProfileData`` does not
expose that stat, so this module reads the ``.xplane.pb`` as the XSpace
proto itself, through TensorFlow's generated ``xplane_pb2`` loaded from
its file (importing TensorFlow would load all of it).

An op lies under scope ``a/b`` where its path holds the components ``a``,
``b`` one after the other; a transform's wrapper, as in ``jvp(a)``, is
taken off a component first.  The device time of a scope is the union of
its ops' intervals inside the window (from the end of the first
``jit_bench_window_mark`` program to the start of the last, as
``trace_reduce`` bounds it), so a loop op that spans its body's ops counts
once; with several chips, the mean over them.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys
import traceback

from .program_spans import TRACE_DIR
from .trace_reduce import MARK, find_xplane, module_name, union

_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("TensorFlow's xplane_pb2 is not installed")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def bare(component: str) -> str:
    """``transpose(jvp(mamba2))`` -> ``mamba2``."""
    while (m := _WRAPPED.match(component)):
        component = m.group(1)
    return component


def read_ops(path):
    """Per TPU plane: (the window marks [(start_ns, end_ns)], the ops
    [(scope path components, start_ns, end_ns)])."""
    pb2 = _xplane_pb2()
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_ids = {m.name: i for i, m in plane.stat_metadata.items()}
        tf_op = stat_ids.get("tf_op")
        paths = {}
        for i, md in plane.event_metadata.items():
            for st in md.stats:
                if st.metadata_id == tf_op:
                    text = st.str_value or (
                        plane.stat_metadata[st.ref_value].name
                        if st.ref_value else "")
                    # the last component is "<op>:<op type>"
                    parts = text.rsplit(":", 1)[0].split("/")
                    paths[i] = tuple(bare(c) for c in parts)
        marks, ops = [], []
        for line in plane.lines:
            for e in line.events:
                s = line.timestamp_ns + e.offset_ps // 1000
                end = s + e.duration_ps // 1000
                if line.name == "XLA Modules" and module_name(
                        plane.event_metadata[e.metadata_id].name) == MARK:
                    marks.append((s, end))
                elif line.name == "XLA Ops" and e.metadata_id in paths:
                    ops.append((paths[e.metadata_id], s, end))
        out.append((sorted(marks), ops))
    return out


def under(path, scope) -> bool:
    """Whether the components of ``scope`` appear in ``path`` in a row."""
    want = tuple(scope.split("/"))
    n = len(want)
    return any(path[i:i + n] == want for i in range(len(path) - n + 1))


def scope_seconds(planes, scope):
    """Device seconds of the ops under ``scope`` in the window, the mean
    over the TPU planes; None where the window's marks are missing."""
    total = []
    for marks, ops in planes:
        if len(marks) < 2:
            return None
        lo, hi = marks[0][1], marks[-1][0]
        total.append(sum(e - s for s, e in union(
            (max(s, lo), min(e, hi)) for p, s, e in ops
            if e > lo and s < hi and under(p, scope))))
    return sum(total) / len(total) / 1e9 if total else None


def window_seconds(run, scope):
    """Device seconds under ``scope`` in ``run``'s traced window, from the
    profile read once per run; None where the run was not traced or the
    profile cannot be read (logged)."""
    if getattr(run, "trace", None) is None:
        return None
    if not hasattr(run, "scoped_ops"):
        run.scoped_ops = None
        try:
            run.scoped_ops = read_ops(find_xplane(TRACE_DIR))
        except Exception:                 # noqa: BLE001 — a metric reader
            traceback.print_exc()         # must not fail the run
            print("scopes: the profile could not be read", file=sys.stderr)
    if run.scoped_ops is None:
        return None
    return scope_seconds(run.scoped_ops, scope)
