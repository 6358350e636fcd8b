"""Degradation ladder for the LGD pipeline: a small health-state machine.

The paper's wall-clock argument only holds if the adaptive machinery
never stalls a long run.  Related weighted-sampling work (Needell &
Ward's batched weighted SGD; online learning-to-sample) gives the safe
landing zone: UNIFORM sampling with weight 1 is always an unbiased
gradient estimator — strictly worse variance than a healthy LSH index,
but never wrong.  The ladder therefore degrades through states that
trade variance for survival, and climbs back when the index heals:

    healthy ──refresh failure──────────────▶ stale-index
    stale-index ──refresh success──────────▶ healthy        (recovered)
    stale-index ──staleness bound hit──────▶ uniform-fallback
    healthy/stale ──fallback-rate spike────▶ uniform-fallback
    healthy/stale ──non-finite loss streak─▶ uniform-fallback
    uniform-fallback ──rebuild succeeds────▶ healthy        (recovered)

STALE-INDEX: the periodic refresh failed (after retries), so draws keep
coming from the last good (features, index) buffer.  Still unbiased —
Algorithm 1's probabilities are exact w.r.t. the INDEXED vectors; the
staleness only costs sampling adaptivity (the index lags the model by
more than one refresh period).  A bounded staleness counter caps how
long this is tolerated.

UNIFORM-FALLBACK: the index is unusable (staleness bound exceeded, the
fallback rate spiked — an index that mostly misses is pure overhead —
or losses went non-finite).  The pipeline emits uniform batches with
weight 1: unbiased by construction, zero dependence on the LSH state.
Every ``recover_after`` steps the pipeline attempts a full canonical
index rebuild; on success the ladder returns to healthy.

``transitions`` records every edge as ``(step, from, to, reason)`` —
surfaced into the trainer's ``metrics_history`` so a production run's
degradation/recovery story is auditable after the fact.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

HEALTHY = "healthy"
STALE_INDEX = "stale-index"
UNIFORM_FALLBACK = "uniform-fallback"

# cluster-level ladder (multi-host deployments; see
# repro.dist.multihost and docs/ARCHITECTURE.md "Multi-host
# deployment & failure model")
CLUSTER_HEALTHY = "healthy"
CLUSTER_DEGRADED = "missing-host-degraded"
CLUSTER_REFORMED = "reformed"


@dataclasses.dataclass
class HealthConfig:
    """Thresholds driving the degradation ladder."""

    # consecutive FAILED refreshes tolerated in stale-index mode before
    # degrading to uniform-fallback (the bounded-staleness contract: the
    # index is never more than (1 + max_stale_refreshes) refresh
    # periods behind the model).
    max_stale_refreshes: int = 3
    # a batch whose uniform-fallback rate exceeds this counts as a
    # strike (the index resolved almost nothing); ``fallback_strikes``
    # consecutive strikes degrade to uniform-fallback.
    fallback_spike: float = 0.9
    fallback_strikes: int = 3
    # consecutive non-finite losses reported by the trainer before the
    # pipeline stops trusting its weighted batches.
    nonfinite_strikes: int = 3
    # steps between index-rebuild attempts while in uniform-fallback.
    recover_after: int = 25


class HealthMonitor:
    """Tracks one pipeline's position on the degradation ladder.

    Pure bookkeeping — the PIPELINE owns the behaviour (which buffer to
    draw from, when to attempt a rebuild); this object decides only the
    state, so the transition logic is testable without JAX anywhere.
    """

    def __init__(self, cfg: HealthConfig = HealthConfig()):
        self.cfg = cfg
        self.state = HEALTHY
        self.stale_refreshes = 0       # consecutive failed refreshes
        self.refresh_failures = 0      # lifetime failed refresh attempts
        self.recoveries = 0            # lifetime degraded -> healthy edges
        self.refreshes = 0             # lifetime committed refreshes
        # every exception a refresh, retry or recovery caught (and logged)
        # instead of raising: a run that must not degrade checks it is 0.
        self.caught_errors = 0
        self.last_error = ""
        self._fallback_strikes = 0
        self._nonfinite_strikes = 0
        self._entered_fallback_step = 0
        self.transitions: List[Tuple[int, str, str, str]] = []

    # -- transitions ---------------------------------------------------------

    def _move(self, step: int, to: str, reason: str):
        if to == self.state:
            return
        self.transitions.append((step, self.state, to, reason))
        if to == HEALTHY and self.state != HEALTHY:
            self.recoveries += 1
        self.state = to
        if to == UNIFORM_FALLBACK:
            self._entered_fallback_step = step
        if to == HEALTHY:
            self.stale_refreshes = 0
            self._fallback_strikes = 0
            self._nonfinite_strikes = 0

    # -- signals -------------------------------------------------------------

    def note_refresh_success(self, step: int):
        self.refreshes += 1
        self.stale_refreshes = 0
        if self.state == STALE_INDEX:
            self._move(step, HEALTHY, "refresh recovered")

    def note_refresh_failure(self, step: int, reason: str = ""):
        """A refresh failed AFTER retries were exhausted."""
        self.refresh_failures += 1
        if self.state == UNIFORM_FALLBACK:
            return
        self.stale_refreshes += 1
        if self.stale_refreshes > self.cfg.max_stale_refreshes:
            self._move(step, UNIFORM_FALLBACK,
                       f"staleness bound exceeded "
                       f"({self.stale_refreshes} failed refreshes)")
        else:
            self._move(step, STALE_INDEX,
                       f"refresh failed: {reason}" if reason
                       else "refresh failed")

    def note_fallback_rate(self, step: int, rate: float):
        """Feed a recent batch's uniform-fallback fraction (sampler_stats
        path) — an index that mostly misses is pure overhead."""
        if self.state == UNIFORM_FALLBACK:
            return
        if rate >= self.cfg.fallback_spike:
            self._fallback_strikes += 1
            if self._fallback_strikes >= self.cfg.fallback_strikes:
                self._move(step, UNIFORM_FALLBACK,
                           f"fallback-rate spike ({rate:.2f} for "
                           f"{self._fallback_strikes} checks)")
        else:
            self._fallback_strikes = 0

    def note_loss(self, step: int, finite: bool):
        """Feed the trainer's per-step loss finiteness."""
        if not finite:
            self._nonfinite_strikes += 1
            if self.state != UNIFORM_FALLBACK and \
                    self._nonfinite_strikes >= self.cfg.nonfinite_strikes:
                self._move(step, UNIFORM_FALLBACK,
                           f"non-finite loss streak "
                           f"({self._nonfinite_strikes})")
        else:
            self._nonfinite_strikes = 0

    def note_caught(self, reason: str):
        """An exception was caught on the refresh path (even one a retry
        then recovered from)."""
        self.caught_errors += 1
        self.last_error = reason

    def note_recovered(self, step: int, reason: str = "index rebuilt"):
        self._move(step, HEALTHY, reason)

    # -- queries -------------------------------------------------------------

    @property
    def degraded(self) -> bool:
        return self.state != HEALTHY

    def should_attempt_recovery(self, step: int) -> bool:
        """In uniform-fallback, rebuild every ``recover_after`` steps."""
        if self.state != UNIFORM_FALLBACK:
            return False
        waited = step - self._entered_fallback_step
        return waited > 0 and waited % max(self.cfg.recover_after, 1) == 0

    def summary(self) -> dict:
        return {
            "state": self.state,
            "stale_refreshes": self.stale_refreshes,
            "refreshes": self.refreshes,
            "refresh_failures": self.refresh_failures,
            "caught_errors": self.caught_errors,
            "last_error": self.last_error,
            "recoveries": self.recoveries,
            "transitions": list(self.transitions),
        }


class ClusterHealthMonitor:
    """Cluster-level extension of the ladder for multi-host LGD.

    One level above ``HealthMonitor``: where the per-pipeline ladder
    tracks a single index's refresh health, this tracks the MEMBERSHIP
    of the training cluster itself:

        healthy ──host loss detected─────────▶ missing-host-degraded
        missing-host-degraded ──reform done──▶ reformed
        reformed ──host loss detected────────▶ missing-host-degraded

    MISSING-HOST-DEGRADED: a peer stopped heartbeating (or never
    cleared its collective barrier within the bounded retries).  The
    survivors keep training: each adopts the lost host's corpus shard
    (``ShardedLSHPipeline.adopt_shards``), and because the shard bounds
    and shard count are unchanged the composed w = S/(p·N) weights stay
    exactly unbiased mid-incident — only wall-clock per step and
    mid-incident bit-determinism are sacrificed.

    REFORMED: the survivors restored the newest verified checkpoint and
    rebuilt the pipeline with the surviving shard count
    (``rebuild_sharded_pipeline``) — a fully deterministic state again,
    bit-identical to a fresh restore on the same mesh.  Operationally
    equivalent to healthy, kept distinct so an audit of ``transitions``
    shows the membership history at a glance.

    Like ``HealthMonitor`` this is pure bookkeeping: the CLUSTER
    (``repro.dist.multihost.ElasticCluster``) owns detection and the
    reform sequence; this object only decides the state, so the ladder
    is testable without processes or JAX anywhere.  ``transitions``
    records state edges as ``(step, from, to, reason)``; ``events``
    records non-edge incidents (shard adoptions, membership changes).
    """

    def __init__(self):
        self.state = CLUSTER_HEALTHY
        self.lost_hosts: List[int] = []    # lifetime lost ranks
        self.reforms = 0                   # lifetime completed reforms
        self.transitions: List[Tuple[int, str, str, str]] = []
        self.events: List[Tuple[int, str, str]] = []

    def _move(self, step: int, to: str, reason: str):
        if to == self.state:
            return
        self.transitions.append((step, self.state, to, reason))
        self.state = to

    # -- signals -------------------------------------------------------------

    def note_host_lost(self, step: int, ranks, reason: str = ""):
        ranks = sorted(int(r) for r in ranks)
        self.lost_hosts.extend(ranks)
        detail = f"lost host(s) {ranks}" + (f": {reason}" if reason else "")
        self.events.append((step, "host-lost", detail))
        self._move(step, CLUSTER_DEGRADED, detail)

    def note_adopted(self, step: int, shard: int, by_rank: int):
        """A surviving rank took over a lost host's corpus shard (the
        mid-incident unbiasedness move — not a state edge)."""
        self.events.append(
            (step, "shard-adopted",
             f"shard {shard} adopted by rank {by_rank}"))

    def note_reformed(self, step: int, n_shards: int):
        self.reforms += 1
        self._move(step, CLUSTER_REFORMED,
                   f"reformed on {n_shards} shard(s) from verified "
                   f"checkpoint at step {step}")

    # -- queries -------------------------------------------------------------

    @property
    def degraded(self) -> bool:
        return self.state == CLUSTER_DEGRADED

    def summary(self) -> dict:
        return {
            "state": self.state,
            "lost_hosts": list(self.lost_hosts),
            "reforms": self.reforms,
            "transitions": list(self.transitions),
            "events": list(self.events),
        }
