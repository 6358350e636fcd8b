"""LGD at deep-learning scale: LSH-sampled data pipeline (paper Sec. 3.2/App. E).

The paper's BERT recipe, integrated as a first-class pipeline feature:

  * each training example owns a FEATURE VECTOR (for BERT: the pooled
    last-layer representation; here: any per-example embedding the model
    exposes — see ``repro.models.lm.pooled_features``).  Features are
    hashed into the LSH index.
  * the QUERY at step t is derived from the output-layer parameters
    (paper: the classification-layer weights) — as the model changes, the
    query changes, but the tables are only refreshed every
    ``refresh_every`` steps ("the representations do not change
    drastically in every iteration so we can periodically update them").
  * each batch is drawn by Algorithm 1 (m independent samples), and the
    per-sample probabilities become importance weights 1/(p_i N) on the
    loss so gradients stay unbiased.

DEVICE-RESIDENT STEP PATH: the token corpus is uploaded to device ONCE
at pipeline build (``self.store``, lane-padded for the kernel gather;
committed via ``dist.sharding.shard_store_device`` to one device of
the shard's data-parallel group), and every ``next_batch`` /
``next_batch_multi`` is a single jitted on-device program
(``core.sampler.sample_gather``): query hash -> fused bucket probe ->
within-bucket draw -> token-row gather -> 1/(p·N) weight computation
(the ``kernels/gather_weight`` Pallas kernel on TPU, its bit-identical
XLA reference elsewhere).  No host numpy touches the per-step loop; the
sharded composer concatenates sub-batches on device under the mesh
(``dist.sharding.compose_sharded_batch`` — per-shard parts are adopted
zero-copy as the shards of the global batch).

REFRESH MODES (``refresh_mode``):
  * ``"full"`` (default) — re-embed + re-hash the whole shard, the
    original periodic-refresh semantics.
  * ``"delta"`` — refresh cost proportional to drift, not to N: the
    pipeline tracks which examples were VISITED since the last refresh
    (a device-side dirty mask updated by every draw) plus a
    drift-sampled remainder (``drift_frac`` of the shard, drawn from the
    refresh key stream so restores stay deterministic), re-embeds and
    re-hashes ONLY that subset, and merges the changed codes into the
    sorted-code index through the previous ``order``
    (``core.tables.refresh_index_delta`` — tie-stable, and bit-identical
    to a full warm-started refresh when every row is dirty).  Dirty
    counts are padded to power-of-two buckets so jit recompilation stays
    bounded.  ``refresh(full=True)`` forces the full path at any time.

OVERLAPPED REFRESH (double buffering): with ``refresh_async=True`` the
periodic refresh runs on a host thread into a second buffer, launched
``refresh_lead`` steps before the swap boundary; the trainer's device
steps keep running while the refresh computes.  The swap happens at a
fixed step boundary (the thread is joined there), so the batch sequence
is bit-deterministic regardless of thread timing.  In delta mode the
dirty mask is snapshotted (and reset) at LAUNCH time: examples visited
during the lead window roll into the next refresh — the same
features-drift-slowly amortisation argument as the lead itself.

SHARD-BY-EXAMPLE SCALE-OUT (1000+ nodes): ``ShardedLSHPipeline`` gives
each data-parallel group its own index over a contiguous corpus shard
(bounds from ``repro.dist.sharding.example_shard_bounds``).  Per-shard
Algorithm-1 sampling with LOCAL importance weights 1/(p_i n_s) is an
unbiased estimator of the shard mean; re-scaling the local weight by
n_s * S / N (i.e. w_i = S / (p_i N)) and concatenating equal-size
per-shard sub-batches makes the plain batch mean equal the average of
shard-mean estimates — exactly what the DP all-reduce of per-device
means computes.  No cross-host hash-table traffic, no O(N) anything per
step: the paper's O(1) property survives scale-out.  Elastic restarts
that change the mesh (and hence shard count) rebuild every per-shard
index bit-deterministically from the restored step — see
``repro.train.elastic.rebuild_sharded_pipeline``.

HASH FAMILY (``LSHPipelineConfig.family``): "srp" (default) keeps the
paper's recipe — feature embeddings row-normalised so cosine SimHash
proxies the inner product — bit-identical to the pre-family pipeline;
"mips" hashes embeddings UN-normalised through the asymmetric
Simple-LSH augmentation (``core.families.mips``), whose collision
probability is monotone in the raw inner product.  Augmentation runs
at build/refresh time on the feature side and once per draw on the
query side, so the per-step jitted sample->gather->weight program is
byte-for-byte the same; the MIPS data scale M is pinned at each full
(re)build and replayed for delta-refresh subsets (``_feat_scale`` —
async refreshes commit features, index and scale together at the swap
boundary, so a failed refresh cannot leave them out of sync).

SELF-HEALING (the degradation ladder — see ``repro.data.health``): a
refresh that raises is retried with exponential backoff + deterministic
jitter (``refresh_retries`` / ``refresh_backoff``); a refresh worker
that HANGS is abandoned by a watchdog (``refresh_timeout``) and counts
as a failed attempt.  On exhausted retries the pipeline enters
STALE-INDEX mode: it keeps drawing from the last good (features,
index) buffer — still unbiased w.r.t. the indexed vectors — instead of
re-raising at the swap boundary, with a bounded staleness counter.
Past the staleness bound (or on a fallback-rate spike / non-finite-loss
streak reported by the trainer) it degrades to UNIFORM-FALLBACK:
batches are drawn uniformly with weight 1 (unbiased by construction,
zero LSH dependence) from the same per-step key stream, and every
``recover_after`` steps a full canonical index rebuild is attempted;
on success the ladder returns to healthy.  All transitions are recorded
in ``health.transitions`` and surfaced through the trainer's metrics.
Fault injection for tests/chaos drills hooks in via
``set_fault_injector`` (see ``repro.testing.faults``).

STREAMING CORPORA (``streaming=True`` / ``window=``): the token store,
feature buffer and index become CAPACITY-MANAGED device buffers sized
to powers of two (``min_capacity`` floor).  Dead slots hash to the
sentinel ``EMPTY_CODE`` and cluster at every table's sorted tail, so
bucket probes and the uniform fallback only ever see live rows, and
capacity changes (grow on append past capacity, compact when
n_live <= capacity/4) are the ONLY recompile points — mutation
batches are padded to power-of-two id buckets exactly like delta
refresh.  All index mutations go through ONE entry point,
``mutate(IndexMutation(...))`` with an explicit op (``append`` /
``evict`` / ``delta`` / ``refresh`` / ``build``);
``append_rows(tokens)`` / ``evict_rows(ids)`` are the typed
conveniences behind it.  Appended rows are embedded at the pinned
family scale and tie-stably merged through the previous sort order
(the same contract as delta refresh); evictions are sentinel merges.
Per-draw weights become 1/(p·n_live) with n_live a TRACED scalar —
live-count changes do not recompile the step program — so the
estimator stays exactly unbiased as the window advances; with
``window=`` set, appends auto-evict the oldest live rows first.
Mutations compose with the async double-buffered refresh: the launch
snapshots (store, live mask, capacity); mutations during the flight
apply to the live buffers AND are recorded as touched slots; at the
swap boundary the committed result is reconciled by one delta merge
over the touched slots (a capacity change in flight discards the
worker's result and refreshes synchronously on current state).
Explicit mutations are recorded in a MUTATION LOG
(``mutation_log()`` / ``load_mutation_log``): ``restore_at(t)``
truncates the log to entries with step <= t, replays MEMBERSHIP only
(window evictions, growth and compaction are re-derived
deterministically; no embeds) and then rebuilds the index canonically
from restored params — restored-at-step-t bit-determinism survives
streaming.  Slot ids are reused after eviction and remapped by
compaction: ``example_ids`` identify live store rows, not immortal
examples.

KEY DISCIPLINE: all randomness derives from the constructor key by
``fold_in`` with distinct stream salts (build / per-step sampling /
per-refresh), never by chained ``split``.  The determinism contract is
that any two pipelines restored at the same step draw bit-identical
batch sequences (what elastic restarts rely on).  A restore does NOT in
general replay the uninterrupted run: ``restore_at`` re-embeds features
from the restored-step params, rebuilds the index canonically (fresh
argsort, not the history-dependent warm-start chain) and clears the
dirty mask, so batches match the uninterrupted run only when the
embedded features are unchanged — e.g. params-independent feature hooks
(then every refresh, full or delta, is an index no-op and the two runs
coincide bitwise; pinned by tests/test_sharded_lgd.py).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
import warnings
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (
    EMPTY_CODE,
    IndexMutation,
    LSHParams,
    get_family,
    hash_points,
    mutate_index,
    sample_gather,
    sample_gather_batched,
)
from repro.core.tables import LSHIndex, grow_index
from repro.dist.sharding import (
    compose_sharded_batch,
    example_shard_bounds,
    shard_store_device,
)
from repro.kernels import default_use_pallas
from repro.spans import Spans, Totals, merged
from .health import (
    HEALTHY,
    STALE_INDEX,
    UNIFORM_FALLBACK,
    HealthConfig,
    HealthMonitor,
)

log = logging.getLogger("repro.lgd.health")

# fold_in stream salts: one disjoint stream per random consumer, so a
# pipeline's draw at (stream, counter) is independent of how many draws
# other streams made — the restore-at-step property.
_SALT_BUILD = 0x0B11D
_SALT_STEP = 0x057E9
_SALT_REFRESH = 0x0F5E5


def _dirty_bucket(n: int) -> int:
    """Pad a dirty count to a power-of-two bucket (bounded recompiles)."""
    b = 64
    while b < n:
        b <<= 1
    return b


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _pad_mutation(ids: np.ndarray, codes, capacity: int):
    """Pad a mutation batch to a power-of-two id bucket (bounded jit
    recompiles, the delta-refresh trick).  Padding repeats the first
    (id, code) column — a duplicate scatter of identical values, i.e.
    a merge no-op."""
    b = int(ids.shape[0])
    size = min(_dirty_bucket(b), capacity)
    ids_j = jnp.asarray(ids, jnp.int32)
    codes_j = jnp.asarray(codes)
    if size <= b:
        return ids_j, codes_j
    pad = size - b
    ids_p = jnp.concatenate([ids_j, jnp.full((pad,), int(ids[0]),
                                             jnp.int32)])
    codes_p = jnp.concatenate(
        [codes_j, jnp.tile(codes_j[:, :1], (1, pad))], axis=1)
    return ids_p, codes_p


# streaming sharded pipelines space their shards' global example ids by
# a fixed stride (instead of the contiguous initial bounds), so ids stay
# disjoint no matter how far each shard's window advances:
# gid // _SHARD_STRIDE recovers the owning shard, gid % _SHARD_STRIDE
# its local slot.
_SHARD_STRIDE = 1 << 20

_LEGACY_HOOK_MSG = (
    "legacy closure hooks feature_fn(tokens) / query_fn() are "
    "deprecated; pass params= to the pipeline constructor and use the "
    "params-aware flavour feature_fn(params, tokens) / "
    "query_fn(params) (the trainer keeps params fresh via set_params)")


@dataclasses.dataclass
class LSHPipelineConfig:
    k: int = 7                   # paper BERT: K=7
    l: int = 10                  # paper BERT: L=10
    refresh_every: int = 200     # steps between feature re-hash
    minibatch: int = 32
    p_floor: float = 1e-8
    use_pallas: Optional[bool] = None   # None = auto (fused kernels on TPU)
    interpret: bool = False
    # host-side double-buffered refresh: launch the re-embed + re-hash
    # ``refresh_lead`` steps before the swap boundary on a thread so
    # refresh work overlaps device compute.  Deterministic: the swap
    # still happens exactly at the boundary (thread joined there).
    refresh_async: bool = False
    refresh_lead: int = 1
    # "full": re-embed + re-hash the whole shard every refresh.
    # "delta": re-embed + re-hash only the visited-since-last-refresh
    # rows plus a drift-sampled ``drift_frac`` remainder, merged into
    # the index through the previous order (cost ~ drift, not N).
    refresh_mode: str = "full"
    drift_frac: float = 0.05
    # normalise importance weights to mean 1 over the emitted batch
    # (keeps the LR scale of uniform sampling).  Sharded sub-pipelines
    # run with raw weights and normalise once globally.
    normalize_weights: bool = True
    # multi-probe querying: number of ADDITIONAL Hamming-ball probe
    # codes (flip-1 then flip-2 of the packed code) walked per table
    # before the next table draw.  Empty/under-filled buckets then
    # resolve to probability-corrected near-bucket samples instead of
    # uniform fallbacks — weights stay unbiased (core.sampler), the
    # fallback rate drops (tab_optimizers gates this on a skewed
    # corpus).  0 = the paper's single-probe Algorithm 1.
    multiprobe: int = 0
    # LSH family (core.families registry name).  "srp" (default, the
    # pre-family behaviour bit-identically): features are row-L2
    # normalised before hashing so cosine proxies the inner product.
    # "mips": features are hashed UN-normalised through the asymmetric
    # Simple-LSH augmentation — collision probability monotone in the
    # raw inner product, the right family for feature embeddings whose
    # norms carry signal.  Augmentation runs at build/refresh (feature
    # side) and once per draw (query side); the per-step jitted
    # sample->gather->weight program is unchanged.
    family: str = "srp"
    # -- self-healing refresh (module docstring: degradation ladder) --
    # retries after a failed refresh attempt (so 1 + refresh_retries
    # attempts total per refresh cycle) before declaring the cycle
    # failed and entering stale-index mode.
    refresh_retries: int = 2
    # base backoff seconds between retry attempts; attempt j sleeps
    # backoff * 2^(j-1) * (1 + jitter), with the jitter derived
    # deterministically from (refresh_count, attempt).  0 disables.
    refresh_backoff: float = 0.05
    # watchdog seconds for a refresh computation: an attempt exceeding
    # it is abandoned (daemon thread) and counted as failed.  For the
    # async double-buffered path this is the EXTRA wait at the swap-
    # boundary join (the worker already had ``refresh_lead`` steps).
    # None = wait forever (no watchdog).
    refresh_timeout: Optional[float] = None
    # degradation-ladder thresholds; None = HealthConfig() defaults.
    health: Optional[HealthConfig] = None
    # -- streaming corpora (module docstring: STREAMING CORPORA) --
    # capacity-managed store + the mutate()/append_rows()/evict_rows()
    # index-mutation API.  Setting ``window`` implies streaming.
    streaming: bool = False
    # sliding window: appends past ``window`` live rows auto-evict the
    # oldest rows first.  None = unbounded (explicit evicts only).
    window: Optional[int] = None
    # smallest (power-of-two) store capacity; compaction never shrinks
    # below it.
    min_capacity: int = 64

    def __post_init__(self):
        if self.refresh_mode not in ("full", "delta"):
            raise ValueError(
                f"refresh_mode must be 'full' or 'delta', "
                f"got {self.refresh_mode!r}")
        if self.multiprobe < 0:
            raise ValueError(
                f"multiprobe must be >= 0, got {self.multiprobe}")
        if self.refresh_retries < 0:
            raise ValueError(
                f"refresh_retries must be >= 0, got {self.refresh_retries}")
        if self.window is not None:
            if self.window < 1:
                raise ValueError(f"window must be >= 1, got {self.window}")
            self.streaming = True
        if self.streaming:
            cw = get_family(self.family).code_width(self.k)
            if cw > 31:
                # the sentinel capacity model needs every packed code —
                # including a banded family's high-bit band tags — to
                # sort strictly before EMPTY_CODE = 2^32 - 1.
                raise ValueError(
                    f"streaming requires code_width(k) <= 31 (sentinel "
                    f"codes), got {cw} (k={self.k}, "
                    f"family={self.family!r})")
            if self.min_capacity < 1 or (
                    self.min_capacity & (self.min_capacity - 1)):
                raise ValueError(
                    f"min_capacity must be a power of two >= 1, "
                    f"got {self.min_capacity}")
        get_family(self.family)   # raises on unknown family names


class LSHSampledPipeline:
    """Adaptive example sampler over a (local shard of a) token corpus.

    ``feature_fn`` / ``query_fn`` come in two flavours:
      * legacy closures: ``feature_fn(tokens)``, ``query_fn()`` — params
        are baked into the closure.  DEPRECATED: constructing without
        ``params=`` warns (DeprecationWarning) and the flavour will be
        removed; migrate to the params-aware hooks.
      * params-aware (pass ``params=`` to the constructor):
        ``feature_fn(params, tokens)``, ``query_fn(params)`` — the
        trainer pushes fresh params via ``set_params`` after every step,
        so queries always reflect the live model and refreshes re-embed
        with the params current at refresh-launch time.

    ``store_device`` pins the device-resident token store (and hence all
    per-step sampling compute, features and index) to one device — the
    sharded owner passes each shard's DP-group device
    (``shard_store_device``).  With params on a mesh, each embed chunk
    runs on the mesh and its features come back to that device.

    Args:
      key: constructor PRNG key; ALL pipeline randomness derives from
        it via salted fold_in streams (module docstring).
      tokens: (N, S+1) int32 local token shard, uploaded to device once.
      feature_fn / query_fn: per-example embedding and query hooks
        (legacy closures or params-aware — see above).
      config: ``LSHPipelineConfig`` (refresh policy, minibatch,
        ``multiprobe``, kernel dispatch).
      feature_batch: embed chunk size for the corpus re-embeds.
      params: initial model params; passing them selects the
        params-aware hook flavour.
      example_offset: lifts store-local row ids to global example ids
        (sharded owner passes the shard's lower bound).
      store_device: optional device for the token store.

    Determinism: two pipelines built with the same (key, tokens,
    config) draw bit-identical batch sequences, and ``restore_at(t)``
    rewinds to step t's stream positions (elastic restarts rely on
    both).  ``sampler_stats()`` exposes cumulative fallback /
    primary-miss rates without touching the step path.
    """

    def __init__(
        self,
        key: jax.Array,
        tokens: np.ndarray,                  # (N, S+1) local shard
        feature_fn: Callable,
        query_fn: Callable,
        config: LSHPipelineConfig,
        feature_batch: int = 512,
        params: Any = None,
        example_offset: int = 0,
        store_device=None,
        _warn_legacy: bool = True,
    ):
        if params is None and _warn_legacy:
            warnings.warn(_LEGACY_HOOK_MSG, DeprecationWarning,
                          stacklevel=2)
        self.cfg = config
        self.family = get_family(config.family)
        self.tokens = tokens
        self.n = tokens.shape[0]
        self.streaming = config.streaming
        # the device-resident example store: uploaded exactly once; every
        # subsequent step gathers from it on device.  On the Pallas
        # gather path the row width is lane-padded HERE, once, so the
        # kernel wrapper's per-call pad is zero-width and compiles away
        # (``row_width`` keeps the logical S+1 for slicing).  Streaming
        # pipelines additionally pad ROWS up to the power-of-two
        # capacity (dead slots excluded from the index by the sentinel).
        self.row_width = tokens.shape[1]
        self._store_device = store_device
        self._init_membership(tokens)
        self.feature_fn = feature_fn
        self.query_fn = query_fn
        self.feature_batch = feature_batch
        self.params = params
        self._params_aware = params is not None
        self.example_offset = example_offset
        self._base_key = key
        self._step_stream = jax.random.fold_in(key, _SALT_STEP)
        self._refresh_stream = jax.random.fold_in(key, _SALT_REFRESH)
        self._build_key = jax.random.fold_in(key, _SALT_BUILD)
        self._step = 0
        self._refresh_count = 0
        # lgd/... spans of the draw, index/... of the refresh (the
        # refresh worker writes only index/refresh, index/embed and
        # index/rehash)
        self.spans = Spans()
        self._refresh_thread: Optional[threading.Thread] = None
        self._refresh_box: Optional[dict] = None
        # snapshot of the async refresh's inputs, kept until the swap
        # boundary so a failed/hung worker can be retried synchronously
        # on bit-identical inputs.
        self._refresh_snapshot: Optional[tuple] = None
        self._health_cfg = config.health or HealthConfig()
        self.health = HealthMonitor(self._health_cfg)
        self.fault_injector = None         # repro.testing.faults hook
        self._uniform_fn = None            # lazy jit: uniform-fallback draw
        self._track_dirty = (config.refresh_mode == "delta"
                             and config.refresh_every > 0)
        self._dirty = jnp.zeros((self.capacity,), jnp.bool_)
        # streaming: explicit-mutation log (restore_at replays it) and
        # the touched-slot set reconciled at async swap boundaries.
        self._mutlog: List[dict] = []
        self._touched: set = set()
        # sampling diagnostics: device-side lazy accumulators (no sync
        # on the step path; syncs happen only when sampler_stats() is
        # read, e.g. at the trainer's log cadence).
        self._stat_draws = 0
        self._fallback_sum = jnp.zeros((), jnp.int32)
        self._primary_miss_sum = jnp.zeros((), jnp.int32)
        self._last_fallback = jnp.zeros((), jnp.float32)
        # asymmetric-family scale (MIPS: the max feature norm M), pinned
        # at each FULL (re)build so partial re-augmentations (delta
        # refresh) stay consistent with the indexed vectors.
        self._feat_scale = None
        self.features = self._compute_features()
        dim = self.features.shape[-1]          # post-augmentation dim
        # "srp" instantiates the registry's dense-SRP entry under its
        # canonical LSHParams name — bit-identical to the pre-family
        # pipeline (pinned by tests/test_families.py).
        lsh_family = "dense" if config.family == "srp" else config.family
        self.lsh = LSHParams(k=config.k, l=config.l, dim=dim,
                             family=lsh_family)
        self.index: LSHIndex = mutate_index(
            None,
            IndexMutation("build", key=self._build_key,
                          x_aug=self.features, live_mask=self._live_dev),
            self.lsh,
            use_pallas=config.use_pallas, interpret=config.interpret)

    # -- membership / capacity (streaming) -----------------------------------

    def _upload_store(self, rows: jnp.ndarray) -> jax.Array:
        """Lane-pad + device-place a (cap, row_width) token block."""
        if (self.cfg.use_pallas if self.cfg.use_pallas is not None
                else default_use_pallas()):
            rows = jnp.pad(rows, ((0, 0), (0, (-self.row_width) % 128)))
        return (jax.device_put(rows, self._store_device)
                if self._store_device is not None else rows)

    def _init_membership(self, tokens: np.ndarray):
        """(Re)initialise the store + membership state from the
        construction-time corpus — shared by ``__init__`` and the
        ``restore_at`` replay reset."""
        n0 = tokens.shape[0]
        store = jnp.asarray(tokens, jnp.int32)
        if self.streaming:
            cap = max(_next_pow2(max(n0, 1)), self.cfg.min_capacity)
            store = jnp.pad(store, ((0, cap - n0), (0, 0)))
            self.capacity = cap
            self._live_np = np.zeros((cap,), np.bool_)
            self._live_np[:n0] = True
            self._arrival = np.full((cap,), -1, np.int64)
            self._arrival[:n0] = np.arange(n0)
            self._next_arrival = n0
            self._free = list(range(n0, cap))
            self._n_live = n0
        else:
            self.capacity = n0
            self._live_np = None
            self._arrival = None
            self._next_arrival = n0
            self._free = []
            self._n_live = n0
        self.store = self._upload_store(store)
        self._sync_live_dev()

    def _sync_live_dev(self):
        """Refresh the device mirrors of the membership state.  The
        live-count scalar is TRACED into the step program, so advancing
        the window never recompiles; non-streaming pipelines keep both
        mirrors at None — the pre-streaming traces, bit-identically."""
        if self.streaming:
            self._live_dev = jnp.asarray(self._live_np)
            self._n_live_dev = jnp.int32(self._n_live)
        else:
            self._live_dev = None
            self._n_live_dev = None

    @property
    def n_live(self) -> int:
        """Live (indexed) example count — ``n`` unless streaming."""
        return self._n_live

    # -- params hook ---------------------------------------------------------

    def set_params(self, params: Any):
        """Point the feature/query hooks at fresh model params (cheap).

        No-op signal for legacy-closure pipelines (constructed without
        ``params=``): their hooks close over params already, so the
        stored value is never passed to them.
        """
        self.params = params

    # -- features -----------------------------------------------------------

    def _embed(self, chunk: jax.Array, params: Any) -> jax.Array:
        if not self._params_aware:
            with self.spans("index/embed"):
                return self.feature_fn(chunk)
        from repro.models.lm import attention_path
        # the attention path ``pooled_features`` takes for these params
        # and rows, so a trace shows which embed calls ran the kernel
        attn = attention_path(params, chunk.shape[1])
        with self.spans("index/embed", attn=attn):
            leaves = jax.tree.leaves(params)
            sh = getattr(leaves[0], "sharding", None) if leaves else None
            if isinstance(sh, NamedSharding):
                # params span a mesh, the store sits on one device of it:
                # embed on the mesh, keep the features beside the store
                chunk = jax.device_put(chunk, NamedSharding(sh.mesh, P()))
            return self._on_store(self.feature_fn(params, chunk))

    def _on_store(self, x: jax.Array) -> jax.Array:
        """``x`` on the store's device (params-derived values: features,
        queries), so every per-shard program spans that one device."""
        if self._store_device is None:
            return x
        return jax.device_put(x, self._store_device)

    def _normalize(self, f: jax.Array) -> jax.Array:
        return f / jnp.maximum(
            jnp.linalg.norm(f, axis=-1, keepdims=True), 1e-30)

    def _compute_features_scaled(self, params: Any = None, store=None,
                                 live=None):
        """(features, scale) for a full-store embed — NO attribute
        writes, so async refresh workers can call it on launch-time
        snapshots (``store``/``live``) and hand the freshly derived
        scale to the swap boundary.

        Symmetric families row-normalise (the pre-family behaviour,
        bit-identical) and return ``scale=None``; asymmetric families
        run ``augment_data`` under a freshly derived data scale M and
        return it.  With a ``live`` mask (streaming) dead rows are
        zeroed BEFORE the scale derivation, so recycled slots holding
        stale tokens never influence M (or the normalised features that
        the sentinel already excludes from every bucket).
        """
        params = self.params if params is None else params
        store = self.store if store is None else store
        w = self.row_width
        outs = []
        for i in range(0, store.shape[0], self.feature_batch):
            outs.append(self._embed(
                store[i:i + self.feature_batch, :w - 1], params))
        raw = jnp.concatenate(outs, axis=0)
        if live is not None:
            raw = jnp.where(live[:, None], raw, 0.0)
        if not self.family.asymmetric:
            return self._normalize(raw), None
        scale = self.family.data_scale(raw)
        return self.family.augment_data(raw, scale=scale), scale

    def _compute_features(self, params: Any = None) -> jax.Array:
        """Embed every local example; family-augmented for hashing.

        Synchronous entry: pins the asymmetric-family scale M alongside
        the returned features (build / sync refresh / restore paths).
        Async refreshes must use ``_compute_features_scaled`` and commit
        features, index and scale together at the swap boundary.
        """
        feats, scale = self._compute_features_scaled(
            params, live=self._live_dev)
        if self.family.asymmetric:
            self._feat_scale = scale
        return feats

    def _embed_rows(self, ids: jax.Array, params: Any,
                    scale=None, store=None) -> jax.Array:
        """Embed a gathered subset of rows (delta refresh / append /
        reconcile), augmented.

        Chunked exactly like ``_compute_features`` so an all-rows subset
        produces bitwise the same features as a full re-embed — for
        asymmetric families at ``scale`` (the pinned M the indexed
        vectors were built with; delta refresh snapshots it at launch).
        """
        store = self.store if store is None else store
        rows = jnp.take(store, ids, axis=0)[:, :self.row_width - 1]
        outs = []
        for i in range(0, rows.shape[0], self.feature_batch):
            outs.append(self._embed(rows[i:i + self.feature_batch], params))
        raw = jnp.concatenate(outs, axis=0)
        if not self.family.asymmetric:
            return self._normalize(raw)
        return self.family.augment_data(raw, scale=scale)

    # -- refresh ------------------------------------------------------------

    def _take_dirty(self) -> jax.Array:
        """Snapshot and clear the dirty mask (refresh claims the dirt)."""
        dirty, self._dirty = (self._dirty,
                              jnp.zeros((self.capacity,), jnp.bool_))
        return dirty

    def _delta_refresh_values(self, kr: jax.Array, params: Any,
                              dirty: jax.Array, features: jax.Array,
                              index: LSHIndex, scale=None, store=None,
                              live=None):
        """(features, index) after a delta refresh of ``dirty`` rows.

        Pure in its explicit inputs so the async thread can run it on a
        launch-time snapshot.  The visited mask is widened by a
        ``drift_frac`` Bernoulli draw from the refresh key stream —
        deterministic per refresh index, so restores replay it — then
        padded to a power-of-two id bucket (duplicate ids are benign:
        identical rows re-embed to identical codes, and the scatter
        writes identical values).  Streaming: the mask is intersected
        with the (snapshot) live mask, so a drift draw never re-embeds
        a dead slot.
        """
        cap = dirty.shape[0]
        if self.cfg.drift_frac > 0.0:
            kd = jax.random.fold_in(kr, 1)
            dirty = jnp.logical_or(
                dirty,
                jax.random.bernoulli(kd, self.cfg.drift_frac, (cap,)))
        if live is not None:
            dirty = jnp.logical_and(dirty, live)
        nd = int(jnp.sum(dirty))
        if nd == 0:
            return features, index
        size = min(_dirty_bucket(nd), cap)
        ids = jnp.flatnonzero(dirty, size=size,
                              fill_value=jnp.argmax(dirty))
        feats_d = self._embed_rows(ids, params, scale=scale, store=store)
        with self.spans("index/rehash"):
            codes_d = hash_points(feats_d, index.projections, self.lsh,
                                  use_pallas=self.cfg.use_pallas,
                                  interpret=self.cfg.interpret)
            new_index = mutate_index(
                index, IndexMutation("delta", ids=ids, codes=codes_d))
        return features.at[ids].set(feats_d), new_index

    # -- refresh resilience --------------------------------------------------

    def set_fault_injector(self, injector):
        """Install a ``repro.testing.faults`` injector (None clears).

        The pipeline fires ``refresh_compute`` (per refresh attempt) and
        ``recover_rebuild`` (per uniform-fallback recovery attempt)
        events through it — deterministic chaos for tests and drills.
        """
        self.fault_injector = injector

    def _fault(self, event: str, **info):
        if self.fault_injector is not None:
            self.fault_injector.fire(event, **info)

    def _sleep_backoff(self, attempt: int):
        """Exponential backoff with DETERMINISTIC jitter: the jitter is
        a pure function of (refresh_count, attempt), so two replays of
        the same faulted run sleep identically (wall time is not part of
        the batch-determinism contract, but keeping it reproducible
        makes chaos drills comparable)."""
        base = self.cfg.refresh_backoff
        if base <= 0 or attempt <= 0:
            return
        j = (zlib.crc32(f"{self._refresh_count}:{attempt}".encode())
             % 1000) / 1000.0
        time.sleep(base * (2 ** (attempt - 1)) * (1.0 + 0.5 * j))

    def _attempt_refresh(self, kr, full, dirty, params, features, index,
                         scale, store, live, attempt: int):
        """ONE refresh attempt on explicit inputs -> (features, index,
        scale).  Attribute-write-free so failed attempts cannot leave
        partially-committed state (features newer than index, or a scale
        out of sync with both).  ``store``/``live`` are launch-time
        snapshots: streaming mutations replace ``self.store`` under the
        worker, and the swap boundary reconciles the delta."""
        self._fault("refresh_compute", refresh=self._refresh_count,
                    attempt=attempt)
        if full:
            feats, new_scale = self._compute_features_scaled(
                params, store=store, live=live)
            with self.spans("index/rehash"):
                new_index = mutate_index(
                    index,
                    IndexMutation("refresh", key=kr, x_aug=feats,
                                  live_mask=live, warm_start=True),
                    self.lsh, use_pallas=self.cfg.use_pallas,
                    interpret=self.cfg.interpret)
            return feats, new_index, new_scale
        feats, new_index = self._delta_refresh_values(
            kr, params, dirty, features, index, scale=scale, store=store,
            live=live)
        return feats, new_index, scale

    def _guarded(self, thunk):
        """Run ``thunk`` under the hang watchdog: with
        ``refresh_timeout`` set it runs on a daemon thread and a run
        exceeding the timeout raises TimeoutError here (the worker is
        abandoned — it only ever writes its private box)."""
        if self.cfg.refresh_timeout is None:
            return thunk()
        box: dict = {}

        def work():
            try:
                box["result"] = thunk()
            except BaseException as e:
                box["error"] = e

        t = threading.Thread(target=work, daemon=True)
        t.start()
        t.join(self.cfg.refresh_timeout)
        if t.is_alive():
            raise TimeoutError(
                f"refresh attempt exceeded watchdog timeout "
                f"{self.cfg.refresh_timeout}s; worker abandoned")
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _retry_refresh(self, kr, full, dirty, params, features, index,
                       scale, store, live, first_error=None,
                       start_attempt=0) -> bool:
        """Retry loop around the refresh computation; commits the
        (features, index, scale) triple atomically on success.

        Returns True on success.  On exhausted retries the pipeline
        STAYS on its last good buffer (stale-index mode: Algorithm 1's
        probabilities remain exact w.r.t. the indexed vectors, the index
        merely lags the model) and the health monitor decides whether
        the staleness bound was crossed — nothing raises at the swap
        boundary.
        """
        attempts = 1 + max(self.cfg.refresh_retries, 0)
        err = first_error
        if first_error is not None:
            self.health.note_caught(repr(first_error))
        for attempt in range(start_attempt, attempts):
            self._sleep_backoff(attempt)
            try:
                feats, new_index, new_scale = self._guarded(
                    lambda: self._attempt_refresh(
                        kr, full, dirty, params, features, index, scale,
                        store, live, attempt))
            except Exception as e:       # noqa: BLE001 — any failure retries
                err = e
                self.health.note_caught(repr(e))
                log.warning("refresh %d attempt %d failed: %r",
                            self._refresh_count, attempt, e)
                continue
            self.features, self.index = feats, new_index
            if self.family.asymmetric:
                self._feat_scale = new_scale
            self.health.note_refresh_success(self._step)
            return True
        log.warning("refresh %d failed after %d attempt(s); keeping stale "
                    "index (last error: %r)", self._refresh_count,
                    attempts - start_attempt, err)
        self.health.note_refresh_failure(self._step, repr(err))
        return False

    def refresh(self, full: Optional[bool] = None) -> bool:
        """Re-embed + re-hash the local shard synchronously.

        ``full=None`` follows ``cfg.refresh_mode``; ``full=True`` forces
        the whole-shard path regardless of mode.  Both paths re-sort
        through the previous ``order`` (warm start / delta merge), so
        the rebuilt index double-buffers cleanly: unchanged codes keep
        their slots.  Failures retry with backoff; on exhaustion the
        last good buffer stays live (returns False, health degrades).
        """
        full = (self.cfg.refresh_mode != "delta") if full is None else full
        kr = jax.random.fold_in(self._refresh_stream, self._refresh_count)
        dirty = self._take_dirty()
        with self.spans("index/refresh", refresh=self._refresh_count,
                        step=self._step):
            ok = self._retry_refresh(kr, full, dirty, self.params,
                                     self.features, self.index,
                                     self._feat_scale, self.store,
                                     self._live_dev)
        self._refresh_count += 1
        return ok

    def _launch_refresh(self):
        """Start the double-buffer refresh on a host thread (overlap)."""
        if self._refresh_thread is not None:
            return
        kr = jax.random.fold_in(self._refresh_stream, self._refresh_count)
        params = self.params          # snapshot: params as of launch step
        full = self.cfg.refresh_mode != "delta"
        dirty = self._take_dirty()    # delta dirt is claimed at launch
        old_index, old_features = self.index, self.features
        old_scale = self._feat_scale  # snapshot: delta re-augments at it
        # streaming: the worker computes on the LAUNCH-time store /
        # membership; mutations landing during the flight go to the live
        # buffers and into ``_touched`` for the swap-boundary reconcile.
        old_store, old_live = self.store, self._live_dev
        old_capacity = self.capacity
        self._touched = set()
        box: dict = {}
        count, step = self._refresh_count, self._step

        def work():
            # attribute-write-free: features/index/scale are committed
            # TOGETHER at the swap boundary, so an errored or abandoned
            # refresh cannot leave self._feat_scale out of sync with
            # the live (features, index) pair.
            try:
                with self.spans("index/refresh", refresh=count, step=step):
                    box["result"] = self._attempt_refresh(
                        kr, full, dirty, params, old_features, old_index,
                        old_scale, old_store, old_live, attempt=0)
            except BaseException as e:   # handled at the swap boundary
                box["error"] = e

        t = threading.Thread(target=work, daemon=True)
        t.start()
        self._refresh_thread, self._refresh_box = t, box
        # the retry path re-runs the worker's computation on the SAME
        # inputs, so a boundary retry is bit-identical to what the
        # worker would have produced.
        self._refresh_snapshot = (kr, full, dirty, params, old_features,
                                  old_index, old_scale, old_store,
                                  old_live, old_capacity)

    def _swap_refresh(self):
        """Join the in-flight refresh and swap buffers (fixed boundary).

        A worker that errored is retried synchronously (same inputs,
        backoff between attempts); one that HANGS past
        ``refresh_timeout`` is abandoned by the watchdog and counted as
        a failed attempt.  Exhausted retries leave the last good buffer
        live (stale-index mode) instead of raising.
        """
        if self._refresh_thread is None:   # e.g. fresh restore: sync path
            self.refresh()
            return
        t, box = self._refresh_thread, self._refresh_box
        snap = self._refresh_snapshot
        t.join(self.cfg.refresh_timeout)
        hung = t.is_alive()
        self._refresh_thread = None
        self._refresh_box = None
        self._refresh_snapshot = None
        (kr, full, dirty, params, features, index, scale, store, live,
         snap_capacity) = snap
        if self.streaming and snap_capacity != self.capacity:
            # a grow/compact landed during the flight: the worker's
            # buffers have the wrong capacity (and compaction remapped
            # slots).  Discard it and refresh synchronously on CURRENT
            # state — the full path, since the claimed dirty mask also
            # predates the remap.
            self._touched = set()
            zero_dirty = jnp.zeros((self.capacity,), jnp.bool_)
            self._retry_refresh(kr, True, zero_dirty, self.params,
                                self.features, self.index,
                                self._feat_scale, self.store,
                                self._live_dev)
            self._refresh_count += 1
            return
        if hung:
            err = TimeoutError(
                f"async refresh worker hung past the swap boundary "
                f"(watchdog {self.cfg.refresh_timeout}s); abandoned")
            log.warning("%s", err)
            ok = self._retry_refresh(kr, full, dirty, params, features,
                                     index, scale, store, live,
                                     first_error=err, start_attempt=1)
        elif "error" in box:
            ok = self._retry_refresh(kr, full, dirty, params, features,
                                     index, scale, store, live,
                                     first_error=box["error"],
                                     start_attempt=1)
        else:
            feats, new_index, new_scale = box["result"]
            self.features, self.index = feats, new_index
            if self.family.asymmetric:
                self._feat_scale = new_scale
            self.health.note_refresh_success(self._step)
            ok = True
        if self.streaming:
            if ok:
                # the committed buffers predate any in-flight mutations;
                # fold them back in with one delta merge.
                self._reconcile_touched()
            else:
                # stale-index mode keeps the LIVE buffers, which already
                # carry every mutation — nothing to reconcile.
                self._touched = set()
        self._refresh_count += 1

    def _reconcile_touched(self):
        """Merge in-flight mutations into a just-committed refresh
        result: touched live slots are re-embedded from the CURRENT
        store at the committed scale and delta-merged; touched dead
        slots are sentinel-merged — one tie-stable merge for both."""
        touched = sorted(self._touched)
        self._touched = set()
        if not touched:
            return
        slots = np.asarray(touched, np.int64)
        live = self._live_np[slots]
        codes = np.full((self.lsh.l, len(slots)), EMPTY_CODE, np.uint32)
        if live.any():
            l_ids = jnp.asarray(slots[live], jnp.int32)
            feats = self._embed_rows(l_ids, self.params,
                                     scale=self._feat_scale)
            codes_l = hash_points(feats, self.index.projections, self.lsh,
                                  use_pallas=self.cfg.use_pallas,
                                  interpret=self.cfg.interpret)
            codes = jnp.asarray(codes).at[:, jnp.asarray(
                np.flatnonzero(live))].set(codes_l)
            self.features = self.features.at[l_ids].set(feats)
        ids_p, codes_p = _pad_mutation(
            np.asarray(slots, np.int32), jnp.asarray(codes), self.capacity)
        self.index = mutate_index(
            self.index, IndexMutation("delta", ids=ids_p, codes=codes_p))

    def _attempt_recovery(self) -> bool:
        """Uniform-fallback -> healthy: try a full CANONICAL index
        rebuild (fresh argsort from the build key, like ``restore_at`` —
        not the refresh-stream warm-start chain, which the failed
        refreshes desynced).  Failure stays in uniform-fallback until
        the next ``recover_after`` boundary."""
        try:
            def build():
                self._fault("recover_rebuild", step=self._step)
                feats, scale = self._compute_features_scaled(
                    self.params, live=self._live_dev)
                idx = mutate_index(
                    None,
                    IndexMutation("build", key=self._build_key,
                                  x_aug=feats, live_mask=self._live_dev),
                    self.lsh,
                    use_pallas=self.cfg.use_pallas,
                    interpret=self.cfg.interpret)
                return feats, idx, scale
            feats, idx, scale = self._guarded(build)
        except Exception as e:           # noqa: BLE001
            log.warning("recovery rebuild failed at step %d: %r",
                        self._step, e)
            self.health.note_caught(repr(e))
            self.health.refresh_failures += 1
            return False
        self.features, self.index = feats, idx
        if self.family.asymmetric:
            self._feat_scale = scale
        self._dirty = jnp.zeros((self.capacity,), jnp.bool_)
        self.health.note_recovered(self._step)
        log.info("recovered at step %d: index rebuilt", self._step)
        return True

    def _discard_refresh(self):
        """Abandon any in-flight refresh worker (it only writes its
        private box) — used when degrading to uniform-fallback, where
        the refresh schedule is suspended."""
        self._refresh_thread = None
        self._refresh_box = None
        self._refresh_snapshot = None

    def note_loss(self, finite: bool):
        """Trainer hook: per-step loss finiteness feeds the ladder (a
        non-finite streak degrades to uniform-fallback)."""
        pre = self.health.state
        self.health.note_loss(self._step, finite)
        if self.health.state != pre and \
                self.health.state == UNIFORM_FALLBACK:
            self._discard_refresh()

    def check_health(self):
        """Feed the latest batch's fallback rate into the ladder (syncs
        a device scalar — call at log cadence, not per step) and return
        the current state."""
        pre = self.health.state
        if self._stat_draws > 0 and pre != UNIFORM_FALLBACK:
            self.health.note_fallback_rate(
                self._step, float(self._last_fallback))
            if self.health.state == UNIFORM_FALLBACK:
                self._discard_refresh()
        return self.health.state

    def health_state(self) -> str:
        return self.health.state

    def health_summary(self) -> dict:
        return self.health.summary()

    def finalize(self):
        """Join any in-flight refresh thread (call before teardown).
        A worker failure that had not yet hit a swap boundary is folded
        into the health state (and logged) rather than raised — teardown
        is resilient by design."""
        if self._refresh_thread is not None:
            self._refresh_thread.join(self.cfg.refresh_timeout)
            box = self._refresh_box or {}
            self._discard_refresh()
            if "error" in box:
                log.warning("in-flight refresh failed at teardown: %r",
                            box["error"])
                self.health.note_caught(repr(box["error"]))
                self.health.note_refresh_failure(
                    self._step, repr(box["error"]))

    def _maybe_refresh(self):
        re = self.cfg.refresh_every
        if re <= 0:
            return
        s = self._step
        if self.cfg.refresh_async and self.cfg.refresh_lead > 0:
            lead = min(self.cfg.refresh_lead, re - 1)
            if s + lead >= re and (s + lead) % re == 0:
                self._launch_refresh()
            if s >= re and s % re == 0:
                with self.spans("index/refresh_join",
                                refresh=self._refresh_count, step=s):
                    self._swap_refresh()
        elif s >= re and s % re == 0:
            self.refresh()

    # -- batches ------------------------------------------------------------

    def _tick(self):
        """Shared refresh gate + per-step key for both batch entry points.

        In uniform-fallback the refresh schedule is suspended (the index
        is not trusted); instead the pipeline periodically attempts a
        full canonical rebuild to climb back to healthy.  The per-step
        key stream advances identically in every state, so a run that
        degrades and recovers stays on the same key schedule as a
        healthy one.
        """
        if self.health.state == UNIFORM_FALLBACK:
            if self.health.should_attempt_recovery(self._step):
                self._attempt_recovery()
        else:
            self._maybe_refresh()
        sub = jax.random.fold_in(self._step_stream, self._step)
        self._step += 1
        return sub

    def _uniform_batch(self, sub: jax.Array, m: int):
        """Uniform-fallback draw: m uniform rows with weight 1.

        Plain Monte-Carlo — E[(1/m)·Σ ∇f_i] over uniform i is the exact
        mean gradient, so weight 1 is unbiased by construction with ZERO
        dependence on the LSH state (Needell & Ward's safe baseline).
        Under sharding the owner rescales by n_s·S/N exactly as for
        weighted batches, which composes shard-means into the global
        mean — no special-casing needed.

        Streaming: the draw is uniform over the LIVE rows — slot u of
        table 0's sorted order for u < n_live (the sentinel clusters
        every dead slot past the live prefix), with store / order /
        n_live passed as traced arguments so mutations never recompile.
        """
        if self.streaming:
            if self._uniform_fn is None:
                off, rw = self.example_offset, self.row_width

                def draw(key, store, order0, n_live, mm):
                    u = jax.random.randint(key, (mm,), 0, n_live)
                    idx = order0[u]
                    rows = jnp.take(store, idx, axis=0)[:, :rw]
                    return {
                        "tokens": rows[:, :-1],
                        "targets": rows[:, 1:],
                        "loss_weights": jnp.ones((mm,), jnp.float32),
                        "example_ids": idx + off,
                    }, idx
                self._uniform_fn = jax.jit(draw, static_argnums=4)
            batch, idx = self._uniform_fn(sub, self.store,
                                          self.index.order[0],
                                          self._n_live_dev, m)
            self._mark_dirty(idx)
            return batch
        if self._uniform_fn is None:
            n, off, rw = self.n, self.example_offset, self.row_width

            def draw(key, mm):
                idx = jax.random.randint(key, (mm,), 0, n)
                rows = jnp.take(self.store, idx, axis=0)[:, :rw]
                return {
                    "tokens": rows[:, :-1],
                    "targets": rows[:, 1:],
                    "loss_weights": jnp.ones((mm,), jnp.float32),
                    "example_ids": idx + off,
                }, idx
            self._uniform_fn = jax.jit(draw, static_argnums=1)
        batch, idx = self._uniform_fn(sub, m)
        self._mark_dirty(idx)
        return batch

    def restore_at(self, step: int, rebuild: bool = True):
        """Elastic/deterministic resume: rewind counters to ``step`` and
        canonically rebuild the index from current params.

        The rebuilt index reuses the original projections (same build
        key) on freshly-embedded features with a fresh argsort — NOT the
        warm-started order chain, which is history-dependent through tie
        layouts.  Two restores at the same step are therefore bitwise
        identical, and the fold_in key streams make every subsequent
        batch identical across restores too.  The dirty mask restarts
        empty: a restored pipeline re-embeds everything, so it owes no
        deferred refresh work.

        ``rebuild=False`` skips the O(N) re-embed + re-hash; valid ONLY
        when the pipeline was just constructed from the restored params
        (its ``__init__`` build is bitwise what the rebuild would
        produce) — the elastic restore path uses this to avoid paying
        the corpus embed twice.

        Streaming: the mutation log is truncated to entries with
        step <= ``step`` and replayed MEMBERSHIP-ONLY (store writes,
        window evictions, growth/compaction — all re-derived
        deterministically, no embeds), then the index is rebuilt
        canonically over the replayed membership; a non-empty replay
        forces ``rebuild=True``.  Two restores at the same step are
        bitwise identical — streaming included.
        """
        self.finalize()
        if self.streaming:
            kept = [e for e in self._mutlog if e["step"] <= step]
            self._init_membership(self.tokens)
            for e in kept:
                if e["op"] == "append":
                    self._apply_append(e["tokens"], with_index=False)
                else:
                    self._apply_evict(
                        np.asarray(e["ids"], np.int64)
                        - self.example_offset, with_index=False)
            self._mutlog = kept
            self._touched = set()
            if kept:
                rebuild = True
        re = self.cfg.refresh_every
        self._step = step
        self._refresh_count = (
            0 if re <= 0 or step < 1 else (step - 1) // re)
        self._dirty = jnp.zeros((self.capacity,), jnp.bool_)
        # a restored pipeline starts HEALTHY: the rebuild below (or the
        # constructor build it mirrors) is a fresh, verified index, and
        # determinism requires replays to be state-independent.
        self.health = HealthMonitor(self._health_cfg)
        self._refresh_snapshot = None
        if rebuild:
            self.features = self._compute_features()
            self.index = mutate_index(
                None,
                IndexMutation("build", key=self._build_key,
                              x_aug=self.features,
                              live_mask=self._live_dev),
                self.lsh,
                use_pallas=self.cfg.use_pallas,
                interpret=self.cfg.interpret)

    # -- index mutations (the unified entry point) ---------------------------

    def _require_streaming(self, what: str):
        if not self.streaming:
            raise ValueError(
                f"{what} requires streaming=True (or window=) in "
                f"LSHPipelineConfig")

    def mutate(self, mutation: IndexMutation):
        """THE index-mutation entry point (explicit op — see
        ``core.tables.IndexMutation``):

          * ``append`` — ``tokens`` (B, S+1): add rows (streaming);
            returns the assigned global example ids.
          * ``evict`` — ``ids``: remove rows by global id (streaming).
          * ``delta`` — refresh only visited + drift rows (the
            ``refresh(full=False)`` path).
          * ``refresh`` — full warm refresh (``refresh(full=True)``).
          * ``build`` — canonical rebuild: re-embed everything and
            fresh-argsort from the build key (what ``restore_at`` and
            fault recovery do); discards any in-flight async refresh.

        ``build``/``refresh``/``delta`` run synchronously here; the
        periodic schedule (``refresh_every`` / ``refresh_async``) is
        unchanged and composes with mutations as described in the
        module docstring.
        """
        op = mutation.op
        if op == "append":
            if mutation.tokens is None:
                raise ValueError("mutate(append) needs tokens=")
            return self.append_rows(mutation.tokens)
        if op == "evict":
            if mutation.ids is None:
                raise ValueError("mutate(evict) needs ids=")
            return self.evict_rows(np.asarray(mutation.ids))
        if op == "refresh":
            return self.refresh(full=True)
        if op == "delta":
            return self.refresh(full=False)
        # op == "build" (IndexMutation validates the op set)
        return self._canonical_rebuild()

    def append_rows(self, tokens) -> np.ndarray:
        """Append token rows to the live window (streaming only).

        Embeds the new rows at the pinned family scale, hashes them and
        tie-stably merges them into every table; with ``window=`` set,
        the oldest live rows are auto-evicted first.  Logged for
        checkpoint replay.  Returns the assigned global example ids
        (slot + ``example_offset``; slots are reused after eviction).
        """
        self._require_streaming("append_rows")
        tokens = np.asarray(tokens, np.int32)
        slots = self._apply_append(tokens, with_index=True)
        self._mutlog.append({"op": "append", "step": self._step,
                             "tokens": tokens.copy()})
        return slots + self.example_offset

    def evict_rows(self, ids) -> None:
        """Evict rows by global example id (streaming only): a sentinel
        merge pushes their slots past every table's live prefix.  Logged
        for checkpoint replay."""
        self._require_streaming("evict_rows")
        ids = np.asarray(ids, np.int64).reshape(-1)
        self._apply_evict(ids - self.example_offset, with_index=True)
        self._mutlog.append({"op": "evict", "step": self._step,
                             "ids": ids.copy()})

    def _apply_append(self, tokens: np.ndarray,
                      with_index: bool) -> np.ndarray:
        """Membership append (+ index merge when ``with_index``) —
        shared verbatim by the live path and the restore replay, so
        window evictions, growth and slot assignment re-derive
        identically."""
        if tokens.ndim != 2 or tokens.shape[1] != self.row_width:
            raise ValueError(
                f"append tokens must be (B, {self.row_width}), "
                f"got {tokens.shape}")
        b = tokens.shape[0]
        if b < 1:
            raise ValueError("append needs at least one row")
        w = self.cfg.window
        if w is not None:
            if b > w:
                raise ValueError(
                    f"append batch {b} exceeds window {w}")
            over = self._n_live + b - w
            if over > 0:
                live_slots = np.flatnonzero(self._live_np)
                oldest = live_slots[np.argsort(
                    self._arrival[live_slots], kind="stable")][:over]
                self._apply_evict(oldest, with_index=with_index)
        if self._n_live + b > self.capacity:
            self._grow(_next_pow2(self._n_live + b), with_index)
        self._free.sort()
        slots = np.asarray(self._free[:b], np.int64)
        del self._free[:b]
        jslots = jnp.asarray(slots, jnp.int32)
        rows = jnp.pad(jnp.asarray(tokens, jnp.int32),
                       ((0, 0), (0, self.store.shape[1] - self.row_width)))
        self.store = self.store.at[jslots].set(rows)
        self._live_np[slots] = True
        self._arrival[slots] = np.arange(self._next_arrival,
                                         self._next_arrival + b)
        self._next_arrival += b
        self._n_live += b
        self._sync_live_dev()
        if with_index:
            feats = self._embed_rows(jslots, self.params,
                                     scale=self._feat_scale)
            codes = hash_points(feats, self.index.projections, self.lsh,
                                use_pallas=self.cfg.use_pallas,
                                interpret=self.cfg.interpret)
            self.features = self.features.at[jslots].set(feats)
            ids_p, codes_p = _pad_mutation(slots.astype(np.int32), codes,
                                           self.capacity)
            self.index = mutate_index(
                self.index,
                IndexMutation("delta", ids=ids_p, codes=codes_p))
            if self._refresh_thread is not None:
                self._touched.update(int(s) for s in slots)
        return slots

    def _apply_evict(self, slots: np.ndarray, with_index: bool):
        """Membership evict (+ sentinel merge when ``with_index``) —
        shared by the live path, window auto-evict and restore replay."""
        slots = np.asarray(slots, np.int64).reshape(-1)
        if slots.size == 0:
            return
        if np.unique(slots).size != slots.size:
            raise ValueError("duplicate ids in evict batch")
        if ((slots < 0) | (slots >= self.capacity)).any() or \
                not self._live_np[slots].all():
            raise ValueError("evict of unknown or already-dead rows")
        self._live_np[slots] = False
        self._arrival[slots] = -1
        self._free.extend(int(s) for s in slots)
        self._n_live -= int(slots.size)
        self._sync_live_dev()
        if with_index:
            size = min(_dirty_bucket(int(slots.size)), self.capacity)
            ids_p = np.concatenate(
                [slots, np.full((size - slots.size,), slots[0])])
            self.index = mutate_index(
                self.index,
                IndexMutation("evict",
                              ids=jnp.asarray(ids_p, jnp.int32)))
            if self._refresh_thread is not None:
                self._touched.update(int(s) for s in slots)
        self._maybe_compact(with_index)

    def _grow(self, new_cap: int, with_index: bool):
        """Grow every capacity-sized buffer to ``new_cap`` (a power of
        two) — one recompile point per doubling, never per append."""
        pad = new_cap - self.capacity
        self.store = jnp.pad(self.store, ((0, pad), (0, 0)))
        self._live_np = np.concatenate(
            [self._live_np, np.zeros((pad,), np.bool_)])
        self._arrival = np.concatenate(
            [self._arrival, np.full((pad,), -1, np.int64)])
        self._free.extend(range(self.capacity, new_cap))
        if with_index:
            self.features = jnp.pad(self.features, ((0, pad), (0, 0)))
            self._dirty = jnp.pad(self._dirty, (0, pad))
            self.index = grow_index(self.index, new_cap)
        self.capacity = new_cap
        self._sync_live_dev()

    def _maybe_compact(self, with_index: bool):
        """Halve capacity once live occupancy drops to a quarter
        (hysteresis: grow doubles at full, compact halves at 1/4, so
        the two never thrash).  Live rows are packed into the prefix in
        ascending slot order — slot ids CHANGE under compaction — and
        the index is rebuilt canonically over the packed features."""
        if not (self._n_live <= self.capacity // 4
                and self.capacity > self.cfg.min_capacity):
            return
        new_cap = self.capacity // 2
        while (self._n_live <= new_cap // 4
               and new_cap > self.cfg.min_capacity):
            new_cap //= 2
        new_cap = max(new_cap, self.cfg.min_capacity)
        live_slots = np.flatnonzero(self._live_np)
        dead_slots = np.flatnonzero(~self._live_np)
        perm = np.concatenate([live_slots, dead_slots])[:new_cap]
        jperm = jnp.asarray(perm, jnp.int32)
        nl = int(live_slots.size)
        self.store = jnp.take(self.store, jperm, axis=0)
        new_live = np.zeros((new_cap,), np.bool_)
        new_live[:nl] = True
        new_arrival = np.full((new_cap,), -1, np.int64)
        new_arrival[:nl] = self._arrival[live_slots]
        self._live_np, self._arrival = new_live, new_arrival
        self._free = list(range(nl, new_cap))
        self.capacity = new_cap
        self._sync_live_dev()
        if with_index:
            self.features = jnp.take(self.features, jperm, axis=0)
            self._dirty = jnp.logical_and(
                jnp.take(self._dirty, jperm), jnp.asarray(new_live))
            self._canonical_rebuild_index()

    def _canonical_rebuild_index(self):
        self.index = mutate_index(
            None,
            IndexMutation("build", key=self._build_key,
                          x_aug=self.features, live_mask=self._live_dev),
            self.lsh,
            use_pallas=self.cfg.use_pallas, interpret=self.cfg.interpret)

    def _canonical_rebuild(self) -> bool:
        """``mutate(build)``: re-embed everything + fresh argsort from
        the build key (the restore/recovery construction)."""
        self._discard_refresh()
        self.features = self._compute_features()
        self._canonical_rebuild_index()
        self._dirty = jnp.zeros((self.capacity,), jnp.bool_)
        return True

    def mutation_log(self) -> list:
        """The explicit-mutation log as JSON-serialisable entries (what
        the trainer checkpoints; ``load_mutation_log`` + ``restore_at``
        replay it)."""
        out = []
        for e in self._mutlog:
            if e["op"] == "append":
                out.append({"op": "append", "step": int(e["step"]),
                            "tokens": np.asarray(e["tokens"],
                                                 np.int32).tolist()})
            else:
                out.append({"op": "evict", "step": int(e["step"]),
                            "ids": [int(i) for i in e["ids"]]})
        return out

    def load_mutation_log(self, entries):
        """Install a checkpointed mutation log; the next ``restore_at``
        replays it (membership-only) before the canonical rebuild."""
        self._require_streaming("load_mutation_log")
        norm = []
        for e in entries:
            if e["op"] == "append":
                norm.append({"op": "append", "step": int(e["step"]),
                             "tokens": np.asarray(e["tokens"], np.int32)})
            elif e["op"] == "evict":
                norm.append({"op": "evict", "step": int(e["step"]),
                             "ids": np.asarray(e["ids"], np.int64)})
            else:
                raise ValueError(f"unknown mutation-log op {e['op']!r}")
        self._mutlog = norm

    def _query(self) -> jax.Array:
        q = self.query_fn(self.params) if self._params_aware \
            else self.query_fn()
        # family query augmentation: SRP normalises (bit-identical to
        # the pre-family pipeline), MIPS appends the zero coordinate.
        return self._on_store(self.family.augment_query(q))

    def _mark_dirty(self, indices: jax.Array):
        if self._track_dirty:
            self._dirty = self._dirty.at[indices.reshape(-1)].set(True)

    def _accum_stats(self, gb):
        """Accumulate per-step sampling diagnostics (device-lazy)."""
        fb = gb.fallback.reshape(-1)
        pm = (gb.probe_code.reshape(-1) != 0)
        self._stat_draws += fb.shape[0]
        self._fallback_sum = self._fallback_sum + jnp.sum(
            fb.astype(jnp.int32))
        self._primary_miss_sum = self._primary_miss_sum + jnp.sum(
            pm.astype(jnp.int32))
        self._last_fallback = jnp.mean(fb.astype(jnp.float32))

    def sampler_stats(self) -> Dict[str, float]:
        """Cumulative sampling diagnostics (syncs; read at log cadence).

        Returns:
          ``draws``: samples drawn since construction;
          ``fallback_rate``: fraction that fell back to uniform 1/N;
          ``primary_miss_rate``: fraction whose exact bucket was empty
          (resolved by a multi-probe neighbour OR by fallback);
          ``last_fallback_rate``: the most recent batch's fallback
          fraction.
        """
        d = max(self._stat_draws, 1)
        return {
            "draws": self._stat_draws,
            "fallback_rate": float(self._fallback_sum) / d,
            "primary_miss_rate": float(self._primary_miss_sum) / d,
            "last_fallback_rate": float(self._last_fallback),
        }

    def draw_inputs(self, key: jax.Array, query: jax.Array):
        """``(args, kwargs)`` of the ``sample_gather`` call that draws one
        batch under ``key`` for the (augmented) ``query`` — what
        ``next_batch`` runs, exposed so a caller can lower or replay the
        exact sample program."""
        return (key, self.index, self.features, query, self.store,
                self.lsh), dict(
            m=self.cfg.minibatch, example_offset=self.example_offset,
            multiprobe=self.cfg.multiprobe, p_floor=self.cfg.p_floor,
            normalize=self.cfg.normalize_weights,
            use_pallas=self.cfg.use_pallas, interpret=self.cfg.interpret,
            row_width=self.row_width, n_live=self._n_live_dev)

    def span_totals(self) -> Totals:
        """``{name: (seconds, calls)}`` of this pipeline's spans."""
        return self.spans.totals()

    def next_batch(self, query: Optional[jax.Array] = None
                   ) -> Dict[str, jax.Array]:
        """Draw one batch — a single jitted on-device program; ``query``
        (already normalised) lets a sharded owner compute the shared
        global query once for all shards."""
        with self.spans("lgd/draw", step=self._step):
            return self._draw(query)

    def _draw(self, query: Optional[jax.Array]) -> Dict[str, jax.Array]:
        """``next_batch`` inside its ``lgd/draw`` span, or inside the
        sharded owner's."""
        if self.streaming and self._n_live == 0:
            raise RuntimeError(
                "cannot draw a batch from an empty streaming window "
                "(append rows first)")
        step = self._step
        sub = self._tick()
        if self.health.state == UNIFORM_FALLBACK:
            with self.spans("lgd/sample", step=step):
                return self._uniform_batch(sub, self.cfg.minibatch)
        if query is None:
            with self.spans("lgd/query", step=step):
                query = self._query()
        with self.spans("lgd/sample", step=step):
            args, kw = self.draw_inputs(sub, self._on_store(query))
            gb = sample_gather(*args, **kw)
            self._mark_dirty(gb.indices)
            self._accum_stats(gb)
        return {
            "tokens": gb.tokens,
            "targets": gb.targets,
            "loss_weights": gb.loss_weights,
            "example_ids": gb.example_ids,
        }

    def next_batch_multi(self, queries: jax.Array) -> list:
        """One batch per query row (multi-chain / perturbed-query training).

        ``queries``: (C, dim).  All C queries are hashed and probed by a
        SINGLE fused bucket-probe pass, and all C·m rows are gathered and
        weighted by a single gather+weight pass
        (``core.sampler.sample_gather_batched``); each chain still gets
        exact per-sample Algorithm-1 probabilities under its own query.
        """
        if self.streaming and self._n_live == 0:
            raise RuntimeError(
                "cannot draw a batch from an empty streaming window "
                "(append rows first)")
        sub = self._tick()
        if self.health.state == UNIFORM_FALLBACK:
            c, m = queries.shape[0], self.cfg.minibatch
            big = self._uniform_batch(sub, c * m)
            return [{k: v[i * m:(i + 1) * m] for k, v in big.items()}
                    for i in range(c)]
        qn = self._on_store(self.family.augment_query(queries))
        gb = sample_gather_batched(
            sub, self.index, self.features, qn, self.store, self.lsh,
            m=self.cfg.minibatch, example_offset=self.example_offset,
            multiprobe=self.cfg.multiprobe,
            p_floor=self.cfg.p_floor,
            normalize=self.cfg.normalize_weights,
            use_pallas=self.cfg.use_pallas,
            interpret=self.cfg.interpret,
            row_width=self.row_width,
            n_live=self._n_live_dev)                 # fields (C, m, ...)
        self._mark_dirty(gb.indices)
        self._accum_stats(gb)
        return [{
            "tokens": gb.tokens[c],
            "targets": gb.targets[c],
            "loss_weights": gb.loss_weights[c],
            "example_ids": gb.example_ids[c],
        } for c in range(queries.shape[0])]


class ShardedLSHPipeline:
    """Shard-by-example LGD: one LSH index per data-parallel corpus shard.

    The global corpus (N examples) is split into ``n_shards`` contiguous
    shards (``example_shard_bounds``); shard s owns an independent
    ``LSHSampledPipeline`` keyed by ``fold_in(key, s)`` over its n_s
    examples, with its token store uploaded once and committed via
    ``shard_store_device`` to one device of DP group s: its features,
    index and every draw live there (the embed alone spans the mesh, as
    the params do), so each store is held once.  Every global batch is
    the concatenation
    of equal-size per-shard sub-batches (minibatch must divide by
    n_shards), laid out so dim 0 slices map shard s's examples to DP
    group s under ``dist.sharding.batch_sharding`` — with a mesh the
    composition is ``compose_sharded_batch``: the per-shard device
    arrays are adopted zero-copy as the shards of the global batch, so
    batch assembly costs no host round-trip and no cross-host traffic.

    UNBIASEDNESS: shard s's local estimator (1/m_s) sum_j g_j / (p_j n_s)
    is unbiased for the shard mean; the emitted global weight is the
    local weight rescaled by n_s * S / N, i.e. w_j = S / (p_j N), which
    makes the plain mean over the whole (m = S * m_s)-example batch equal
    the average of shard-mean estimates — an unbiased estimator of the
    full-corpus mean gradient for ANY shard sizes (each shard estimates
    its shard-sum / (N/S); contiguous balanced bounds keep n_s equal up
    to 1).  With ``normalize_weights`` the composed weights are finally
    scaled to mean 1 over the global batch, preserving relative (and
    cross-shard) weighting.

    Each shard refreshes its own index on the shared schedule — with
    ``refresh_async`` all S refreshes overlap device compute, and with
    ``refresh_mode="delta"`` each shard re-embeds only its own visited
    rows.

    Args:
      key: master PRNG key; shard s is keyed by ``fold_in(key, s)``.
      tokens: (N, S+1) int32 GLOBAL corpus (sharded internally).
      feature_fn / query_fn / config / feature_batch / params: as in
        ``LSHSampledPipeline`` (``config.minibatch`` is the GLOBAL
        batch and must divide by ``n_shards``).
      n_shards: number of per-shard indexes (one per DP group at scale).
      mesh: optional ``jax.sharding.Mesh`` enabling the zero-copy
        sharded batch composition.
      owned_shards: the subset of shard ids THIS process builds and
        draws from (default: all — the single-controller mode).  In the
        multi-controller deployment (``repro.dist.multihost``) process
        r passes ``owned_shards=[r]``: only its own shard's store is
        embedded/hashed/resident here, and ``next_batch`` returns just
        the owned sub-batches — the LOCAL slice of the global batch.
        The emitted weights keep the GLOBAL w = S/(p·N) composition
        (``n_shards`` and the shard bounds are corpus-global), so each
        process's batch is an unbiased estimator of its shards' portion
        and the DP mean across processes of the full corpus.  Partial
        ownership is incompatible with ``streaming`` (remote live
        counts are unknowable locally) and with ``normalize_weights``
        (mean-1 normalisation is a global-batch statistic) — both
        raise.  ``adopt_shards`` extends ownership at runtime (host-
        loss recovery).

    Determinism: as ``LSHSampledPipeline``, per shard — shard s's draw
    stream depends only on ``fold_in(key, s)`` and the params history,
    NOT on which process owns it, so per-process draws compose bitwise
    into the single-controller batch.  ``restore_at`` rewinds every
    owned shard, and a restore onto a DIFFERENT ``n_shards`` (elastic
    reshape) goes through
    ``repro.train.elastic.rebuild_sharded_pipeline``.
    """

    def __init__(
        self,
        key: jax.Array,
        tokens: np.ndarray,                  # (N, S+1) global corpus
        feature_fn: Callable,
        query_fn: Callable,
        config: LSHPipelineConfig,
        n_shards: int = 1,
        feature_batch: int = 512,
        params: Any = None,
        mesh=None,
        owned_shards: Optional[Sequence[int]] = None,
    ):
        if config.minibatch % n_shards != 0:
            raise ValueError(
                f"minibatch={config.minibatch} must divide by "
                f"n_shards={n_shards}")
        if params is None:
            warnings.warn(_LEGACY_HOOK_MSG, DeprecationWarning,
                          stacklevel=2)
        if owned_shards is None:
            owned = list(range(n_shards))
        else:
            owned = sorted({int(s) for s in owned_shards})
            if not owned:
                raise ValueError("owned_shards must not be empty")
            bad = [s for s in owned if not 0 <= s < n_shards]
            if bad:
                raise ValueError(
                    f"owned_shards {bad} not in [0, {n_shards})")
        partial = len(owned) < n_shards
        if partial and config.streaming:
            raise ValueError(
                "owned_shards with streaming=True is unsupported: the "
                "sharded weight composition needs every shard's LIVE "
                "count, which a partial owner cannot observe — run "
                "streaming pipelines with full ownership per process "
                "group (n_shards == len(owned_shards))")
        if partial and config.normalize_weights:
            raise ValueError(
                "owned_shards with normalize_weights=True is "
                "unsupported: mean-1 normalisation is a statistic of "
                "the GLOBAL batch, which a partial owner never sees — "
                "normalise after the cross-process composition instead")
        self.cfg = config
        self.n = tokens.shape[0]
        self.n_shards = n_shards
        self.owned = owned
        self.mesh = mesh
        self.streaming = config.streaming
        # adopt_shards rebuilds missing shards from the construction
        # corpus: keep the ingredients (references, not copies).
        self._key = key
        self._tokens = tokens
        self._feature_fn = feature_fn
        self._query_fn = query_fn
        self._feature_batch = feature_batch
        shard_window = None
        if config.streaming:
            if config.window is not None:
                if config.window % n_shards != 0:
                    raise ValueError(
                        f"window={config.window} must divide by "
                        f"n_shards={n_shards}")
                shard_window = config.window // n_shards
            if self.n // n_shards + 1 >= _SHARD_STRIDE:
                raise ValueError(
                    f"initial shard size {self.n // n_shards + 1} "
                    f"exceeds the streaming id stride {_SHARD_STRIDE}")
        self._shard_cfg = dataclasses.replace(
            config, minibatch=config.minibatch // n_shards,
            normalize_weights=False, window=shard_window)
        self.shards: List[LSHSampledPipeline] = [
            self._make_shard(s, params) for s in self.owned]
        self.spans = Spans()       # lgd/draw, lgd/query and lgd/compose

    def _make_shard(self, s: int, params: Any) -> "LSHSampledPipeline":
        """Build shard ``s``'s pipeline — keyed by ``fold_in(key, s)``
        over its contiguous corpus slice, identically on any owner."""
        lo, hi = example_shard_bounds(self.n, s, self.n_shards)
        # streaming shards address global ids by a fixed per-shard
        # stride (ids stay disjoint as windows advance); static
        # shards keep the contiguous initial bounds bit-compatibly.
        off = s * _SHARD_STRIDE if self.cfg.streaming else lo
        return LSHSampledPipeline(
            jax.random.fold_in(self._key, s), self._tokens[lo:hi],
            self._feature_fn, self._query_fn, self._shard_cfg,
            feature_batch=self._feature_batch, params=params,
            example_offset=off,
            store_device=shard_store_device(self.mesh, s, self.n_shards),
            _warn_legacy=False)

    def adopt_shards(self, shard_ids: Sequence[int], step: int,
                     params: Any = None):
        """Take ownership of additional shards (host-loss recovery).

        The multi-controller incident path: a peer process died, so the
        survivor adopts its shard(s) — builds the missing per-shard
        pipelines from the construction corpus slice with the same
        ``fold_in(key, s)`` key streams, embedded from ``params``
        (default: current params), and rewinds them to ``step``.

        UNBIASEDNESS: ``n_shards`` and the shard bounds are unchanged —
        only ownership moved — so the composed weights keep the exact
        global w = S/(p·N) form and E[1/(pN)] stays 1 mid-incident
        (Algorithm 1's probabilities are exact w.r.t. the indexed
        vectors, whatever those vectors are).  DETERMINISM: the adopted
        index is embedded from the CURRENT params, not the lost host's
        refresh history (gone with the host), so mid-incident draws are
        NOT bit-reproducible; the full reform
        (``rebuild_sharded_pipeline`` from a verified checkpoint)
        restores the determinism contract.
        """
        if self.streaming:
            raise ValueError(
                "adopt_shards requires a static corpus (streaming "
                "pipelines run fully-owned per process group)")
        params = self.params if params is None else params
        for s in sorted({int(x) for x in shard_ids}):
            if s in self.owned:
                raise ValueError(f"shard {s} is already owned")
            if not 0 <= s < self.n_shards:
                raise ValueError(
                    f"shard {s} not in [0, {self.n_shards})")
            p = self._make_shard(s, params)
            p.restore_at(step, rebuild=False)
            pos = int(np.searchsorted(np.asarray(self.owned), s))
            self.owned.insert(pos, s)
            self.shards.insert(pos, p)

    @property
    def params(self):
        return self.shards[0].params

    def set_params(self, params: Any):
        for p in self.shards:
            p.set_params(params)

    def restore_at(self, step: int, rebuild: bool = True):
        """Rebuild every per-shard index at ``step`` (elastic restore)."""
        for p in self.shards:
            p.restore_at(step, rebuild=rebuild)

    def finalize(self):
        for p in self.shards:
            p.finalize()

    def refresh(self, full: Optional[bool] = None):
        for p in self.shards:
            p.refresh(full=full)

    # -- index mutations (streaming) -----------------------------------------

    def mutate(self, mutation: IndexMutation):
        """Unified mutation entry (see ``LSHSampledPipeline.mutate``):
        ``append``/``evict`` route across shards; the refresh/build ops
        apply to every shard."""
        op = mutation.op
        if op == "append":
            if mutation.tokens is None:
                raise ValueError("mutate(append) needs tokens=")
            return self.append_rows(mutation.tokens)
        if op == "evict":
            if mutation.ids is None:
                raise ValueError("mutate(evict) needs ids=")
            return self.evict_rows(np.asarray(mutation.ids))
        return [p.mutate(mutation) for p in self.shards]

    def append_rows(self, tokens) -> np.ndarray:
        """Append rows across shards (streaming): each incoming row goes
        to the currently least-live shard (ties to the lowest shard
        index) — deterministic greedy balancing, so per-shard windows
        advance together.  Returns global ids in input-row order."""
        if not self.streaming:
            raise ValueError(
                "append_rows requires streaming=True (or window=) in "
                "LSHPipelineConfig")
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2:
            raise ValueError(f"append tokens must be 2-D, "
                             f"got {tokens.shape}")
        counts = [p.n_live for p in self.shards]
        owner = np.empty((tokens.shape[0],), np.int64)
        for i in range(tokens.shape[0]):
            s = int(np.argmin(counts))
            owner[i] = s
            counts[s] += 1
        gids = np.empty((tokens.shape[0],), np.int64)
        for s, p in enumerate(self.shards):
            rows = np.flatnonzero(owner == s)
            if rows.size:
                gids[rows] = p.append_rows(tokens[rows])
        return gids

    def evict_rows(self, ids) -> None:
        """Evict rows by global id (streaming): ids route to their
        owning shard by ``gid // stride``."""
        if not self.streaming:
            raise ValueError(
                "evict_rows requires streaming=True (or window=) in "
                "LSHPipelineConfig")
        ids = np.asarray(ids, np.int64).reshape(-1)
        owner = ids // _SHARD_STRIDE
        if ((owner < 0) | (owner >= self.n_shards)).any():
            raise ValueError("evict ids outside any shard's id range")
        for s, p in enumerate(self.shards):
            mine = ids[owner == s]
            if mine.size:
                p.evict_rows(mine)

    def mutation_log(self) -> dict:
        """Per-shard mutation logs + the shard count they were routed
        under (replay is only valid on the same ``n_shards``)."""
        return {"n_shards": self.n_shards,
                "shards": [p.mutation_log() for p in self.shards]}

    def load_mutation_log(self, entries: dict):
        if int(entries.get("n_shards", self.n_shards)) != self.n_shards:
            raise ValueError(
                f"mutation log was recorded under n_shards="
                f"{entries.get('n_shards')} but this pipeline has "
                f"n_shards={self.n_shards}; streaming elastic reshape "
                f"is not supported — restore on the recorded shard "
                f"count")
        for p, log_s in zip(self.shards, entries["shards"]):
            p.load_mutation_log(log_s)

    def set_fault_injector(self, injector, shard: Optional[int] = None):
        """Install a fault injector on one shard — a GLOBAL shard id,
        which must be owned here — or on all owned shards (None)."""
        if shard is None:
            targets = self.shards
        else:
            if shard not in self.owned:
                raise ValueError(
                    f"shard {shard} is not owned here (owned: "
                    f"{self.owned})")
            targets = [self.shards[self.owned.index(shard)]]
        for p in targets:
            p.set_fault_injector(injector)

    def note_loss(self, finite: bool):
        for p in self.shards:
            p.note_loss(finite)

    def check_health(self) -> str:
        for p in self.shards:
            p.check_health()
        return self.health_state()

    def health_state(self) -> str:
        """Worst state across shards (one degraded shard degrades the
        reported aggregate — its portion of every batch is affected)."""
        rank = {HEALTHY: 0, STALE_INDEX: 1, UNIFORM_FALLBACK: 2}
        worst = max(self.shards, key=lambda p: rank[p.health.state])
        return worst.health.state

    def health_summary(self) -> dict:
        per = [p.health_summary() for p in self.shards]
        return {
            "state": self.health_state(),
            "stale_refreshes": max(s["stale_refreshes"] for s in per),
            # refreshes every shard has committed
            "refreshes": min(s["refreshes"] for s in per),
            "refresh_failures": sum(s["refresh_failures"] for s in per),
            "caught_errors": sum(s["caught_errors"] for s in per),
            "last_error": next((s["last_error"] for s in reversed(per)
                                if s["last_error"]), ""),
            "recoveries": sum(s["recoveries"] for s in per),
            "transitions": [
                (shard_id,) + tuple(t)
                for shard_id, s in zip(self.owned, per)
                for t in s["transitions"]],
        }

    def sampler_stats(self) -> Dict[str, float]:
        """Draw-weighted aggregate of per-shard sampling diagnostics."""
        per = [p.sampler_stats() for p in self.shards]
        draws = sum(s["draws"] for s in per)
        d = max(draws, 1)
        return {
            "draws": draws,
            "fallback_rate": sum(
                s["fallback_rate"] * s["draws"] for s in per) / d,
            "primary_miss_rate": sum(
                s["primary_miss_rate"] * s["draws"] for s in per) / d,
            "last_fallback_rate": float(
                np.mean([s["last_fallback_rate"] for s in per])),
        }

    def span_totals(self) -> Totals:
        """``{name: (seconds, calls)}`` of the owner's spans and its
        shards', summed."""
        return merged(self.spans.totals(),
                      *(p.span_totals() for p in self.shards))

    def _compose(self, parts: list) -> jax.Array:
        # the zero-copy mesh composition lays out the FULL global batch;
        # a partial owner's batch is its local slice — plain concat.
        if self.mesh is not None and isinstance(self.mesh,
                                                jax.sharding.Mesh) \
                and len(self.owned) == self.n_shards:
            return compose_sharded_batch(parts, self.mesh)
        return jnp.concatenate(parts)

    def next_batch(self) -> Dict[str, jax.Array]:
        step = self.shards[0]._step
        with self.spans("lgd/draw", step=step):
            # the global query is shard-independent: compute + normalise
            # it once and share it across all owned per-shard sample calls
            # (bitwise the same value on every process — query_fn sees
            # only the replicated params, never the shard).
            with self.spans("lgd/query", step=step):
                q = self.shards[0]._query()
            subs = [p._draw(q) for p in self.shards]
            with self.spans("lgd/compose", step=step):
                return self._compose_batch(subs)

    def _compose_batch(self, subs: list) -> Dict[str, jax.Array]:
        m_s = self.cfg.minibatch // self.n_shards
        batch = {
            k: self._compose([b[k] for b in subs])
            for k in ("tokens", "targets", "example_ids")
        }
        # local 1/(p n_s) -> global S/(p N): each sample stands in for
        # N/S corpus examples under the batch mean.  Scaled per shard on
        # the shard's device, composed, then normalised globally — all
        # device ops.  Streaming: n_s and N are the LIVE counts at this
        # draw (the per-shard weights already carry 1/n_live_s), so the
        # composition stays exactly unbiased as the windows advance.
        if self.streaming:
            total_live = sum(p.n_live for p in self.shards)
            w = self._compose([
                b["loss_weights"] * (p.n_live * self.n_shards
                                     / total_live)
                for p, b in zip(self.shards, subs)])
        else:
            w = self._compose([
                b["loss_weights"] * (p.n * self.n_shards / self.n)
                for p, b in zip(self.shards, subs)])
        if self.cfg.normalize_weights:
            w = w / jnp.maximum(jnp.mean(w), 1e-30)
        batch["loss_weights"] = w.astype(jnp.float32)
        batch["shard_ids"] = self._compose([
            jnp.full((m_s,), s, jnp.int32) for s in self.owned])
        return batch


def mean_pool_feature_fn(cfg):
    """Params-aware feature hook: mean-pooled final hidden state
    (the paper's BERT pooled-representation recipe) — pass the result as
    ``feature_fn`` with ``params=`` so the trainer keeps it fresh."""
    from repro.models.lm import pooled_features

    def refresh_embed(params, tokens: jax.Array) -> jax.Array:
        return pooled_features(params, cfg, {"tokens": tokens})
    # compiles as ``jit_refresh_embed``, the name the device trace shows
    return jax.jit(refresh_embed)


def lm_head_query_fn():
    """Params-aware query hook from the output layer (paper: classifier
    weights as queries): the mean lm_head column approximates the
    direction in feature space along which next-token loss is largest."""
    from repro.models.lm import lm_head_query
    return lm_head_query
