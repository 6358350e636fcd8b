"""Sharding rules: logical activation axes + per-parameter placement.

One place owns the mesh mapping so models never name physical axes:

* ``logical(x, *axes)`` annotates activations with *logical* axis names
  ("batch", "seq", "heads", "ff", "vocab", "experts") that resolve to
  physical mesh axes under ``use_mesh``; outside a mesh context it is a
  no-op, so every model runs unsharded on a laptop unchanged.
* ``param_spec(path, shape, mesh)`` assigns a PartitionSpec to one
  parameter from its tree path and shape: tensor-parallel over heads /
  experts / vocab on the ``model`` axis, FSDP over the feature dim on the
  ``data`` axis (``("pod", "data")`` on multi-pod meshes), norms and any
  indivisible dim replicated.  Parameters stacked over layers
  (``blocks/...``) keep their leading layer dim unsharded — it is the
  scan axis.
* ``tree_param_shardings`` maps ``param_spec`` over a whole params (or
  eval_shape) pytree; ``batch_sharding`` shards batch dim 0 over the
  data axes.

Only ``mesh.shape`` / ``mesh.axis_names`` are touched, so tests can pass
stub meshes without building devices.
"""

from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# mesh context
# ---------------------------------------------------------------------------

_MESH: list = []   # stack of active meshes


@contextlib.contextmanager
def use_mesh(mesh):
    """Activate ``mesh`` for ``logical`` constraints within the block."""
    _MESH.append(mesh)
    try:
        yield mesh
    finally:
        _MESH.pop()


def current_mesh():
    return _MESH[-1] if _MESH else None


# ---------------------------------------------------------------------------
# axis resolution
# ---------------------------------------------------------------------------

def _data_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def _axis_size(mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


def data_axis_size(mesh) -> int:
    """Total data-parallel degree of ``mesh`` (pod x data on multi-pod)."""
    return _axis_size(mesh, _data_axes(mesh))


def example_shard_bounds(n: int, shard_id: int, n_shards: int):
    """Contiguous [lo, hi) bounds of corpus shard ``shard_id``.

    Balanced split (sizes differ by at most 1, remainder to the lowest
    ids).  This is the shard-by-example contract for LGD scale-out: DP
    group s builds/refreshes/queries ONLY the LSH index of examples
    [lo, hi) — see ``repro/data/lsh_pipeline.ShardedLSHPipeline`` for
    how per-shard importance weights compose into an unbiased global
    estimator under the DP all-reduce.
    """
    if not (0 <= shard_id < n_shards):
        raise ValueError(f"shard_id {shard_id} not in [0, {n_shards})")
    base, rem = divmod(n, n_shards)
    lo = shard_id * base + min(shard_id, rem)
    hi = lo + base + (1 if shard_id < rem else 0)
    return lo, hi


# logical activation axis -> physical mesh axis ("batch" -> the data axes,
# model-parallel dims -> "model"; "seq" is the sequence-parallel residual
# sharding, also over "model").
_LOGICAL = {
    "batch": _data_axes,
    "seq": lambda mesh: "model",
    "heads": lambda mesh: "model",
    "ff": lambda mesh: "model",
    "vocab": lambda mesh: "model",
    "experts": lambda mesh: "model",
}


def logical(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (no-op meshless)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    assert len(axes) == x.ndim, (axes, x.shape)
    spec = []
    for dim, name in zip(x.shape, axes):
        phys = _LOGICAL[name](mesh) if name is not None else None
        if phys is not None and dim % _axis_size(mesh, phys) != 0:
            phys = None
        spec.append(phys)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# ---------------------------------------------------------------------------
# parameter placement
# ---------------------------------------------------------------------------

def _divisible(mesh, axes, dim: int):
    if axes is None or dim % _axis_size(mesh, axes) != 0:
        return None
    return axes


def param_spec(path: str, shape: tuple, mesh) -> P:
    """PartitionSpec for the parameter at ``path`` with ``shape``.

    Rules (each sharded dim must divide its axes, else replicated):
      embed (V, d)            -> (model, data)     vocab TP + embed FSDP
      lm_head (d, V)          -> (data, model)
      experts_* (E, d, ff)    -> (model, data, -)  expert TP + d FSDP
      wq/wk/wv (d, H, Dh)     -> (data, model, -)  head TP + d FSDP
      wo (H, Dh, d)           -> (model, -, data)
      generic 2-D (din, dout) -> (data, model)     FSDP + output TP
      norms / 1-D             -> replicated
    ``blocks/...`` parameters are stacked over layers: the leading layer
    dim is the scan axis and stays unsharded.
    """
    parts = path.split("/")
    leaf = parts[-1]
    data = _data_axes(mesh)

    stacked = parts[0] == "blocks"
    core = shape[1:] if stacked else shape

    if "norm" in parts or leaf in ("scale", "bias") or len(core) < 2:
        spec = [None] * len(core)
    elif leaf == "embed":
        spec = ["model", data]
    elif leaf == "lm_head":
        spec = [data, "model"]
    elif "experts" in leaf:
        spec = ["model", data] + [None] * (len(core) - 2)
    elif leaf in ("wq", "wk", "wv") and len(core) == 3:
        spec = [data, "model", None]
    elif leaf == "wo" and len(core) == 3:
        spec = ["model", None, data]
    elif len(core) == 2:
        spec = [data, "model"]
    else:
        spec = [None] * len(core)

    spec = [_divisible(mesh, s, d) for s, d in zip(spec, core)]
    if stacked:
        spec = [None] + spec
    return P(*spec)


def _path_str(key_path) -> str:
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def tree_param_shardings(params: Any, mesh):
    """NamedSharding for every leaf of a params (or eval_shape) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, x: NamedSharding(mesh, param_spec(_path_str(kp), x.shape,
                                                     mesh)),
        params,
    )


def batch_sharding(mesh):
    """Batch tensors: dim 0 over the data axes, rest replicated."""
    return NamedSharding(mesh, P(_data_axes(mesh)))


def host_local_mesh(axis_names=("data", "model")):
    """Mesh over THIS process's addressable devices — the surviving
    mesh of a multi-controller deployment after peers are gone.

    The elastic reform path (``repro.dist.multihost``) restores the
    newest verified checkpoint onto whatever devices the survivor still
    addresses; a global mesh would hang on dead hosts' devices, so the
    reform must shard over ``jax.local_devices()`` only.  Returns None
    when a single local device leaves nothing to shard over (callers
    pass ``mesh=None`` downstream — the unsharded path).
    """
    devs = jax.local_devices()
    if len(devs) < 2:
        return None
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(devs).reshape(len(devs), 1), axis_names)


# ---------------------------------------------------------------------------
# device-resident example stores (LGD shard-by-example)
# ---------------------------------------------------------------------------

def shard_store_device(mesh, shard_id: int, n_shards: int):
    """The one device that holds corpus shard ``shard_id``'s store,
    features and index.

    The LGD pipeline uploads each shard's example store ONCE at build
    time; all per-step sampling, gathering and weighting then runs where
    the data lives — no host round-trip.  That device is the first
    (addressable) one of the data-parallel group whose batch rows the
    shard fills under ``batch_sharding``, so ``compose_sharded_batch``
    adopts its sub-batch zero-copy and each store is held once, not on
    every device.  Per-shard programs must span one device: a Pallas
    TPU kernel inside a program partitioned over a mesh is refused.
    The params-aware embed still spans the mesh (see
    ``LSHSampledPipeline._embed``).  Returns None without a mesh
    (single-device hosts: the default device is the only choice).
    """
    if mesh is None:
        return None
    rows = n_shards * data_axis_size(mesh)
    first = shard_id * rows // n_shards      # the shard's first batch row
    where = batch_sharding(mesh).addressable_devices_indices_map((rows,))
    for dev, idx in where.items():
        start, stop, _ = idx[0].indices(rows)
        if start <= first < stop:
            return dev
    return next(iter(where))


def compose_sharded_batch(parts, mesh):
    """Assemble per-shard sub-batches into one global batch — on device.

    ``parts``: equal-length dim-0 slices, part s committed to shard s's
    device (see ``shard_store_device``).  The composed array is exactly
    the concatenation under ``batch_sharding(mesh)``, built with
    ``jax.make_array_from_single_device_arrays`` so a part that already
    sits on its DP group's device is adopted ZERO-COPY; the only
    transfers are device-to-device (model-axis replicas, or shard counts
    that do not match the data-parallel degree).  No host numpy anywhere.
    """
    sh = batch_sharding(mesh)
    rows = sum(p.shape[0] for p in parts)
    shape = (rows,) + tuple(parts[0].shape[1:])
    per = rows // len(parts)

    def pieces(start, stop):
        out, s = [], start // per
        while start < stop:
            take = min(stop, (s + 1) * per) - start
            out.append(parts[s][start - s * per:start - s * per + take])
            start, s = start + take, s + 1
        return out

    arrs = []
    for dev, idx in sh.addressable_devices_indices_map(shape).items():
        start = idx[0].start or 0
        stop = idx[0].stop if idx[0].stop is not None else rows
        ps = [jax.device_put(x, dev) for x in pieces(start, stop)]
        arrs.append(ps[0] if len(ps) == 1 else jax.numpy.concatenate(ps))
    return jax.make_array_from_single_device_arrays(shape, sh, arrs)
