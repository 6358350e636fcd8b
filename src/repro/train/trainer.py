"""Training loop: LGD-sampled or uniform pipeline, checkpoint/restart,
metrics, and the distributed-runtime policies that matter at fleet scale.

Fault-tolerance contract:
  * checkpoint every ``ckpt_every`` steps (async, atomic) including
    optimiser state, data-pipeline step counter and PRNG key -> a
    restarted job resumes bit-deterministically (same batch sequence).
  * ``Trainer(..., resume=True)`` picks up the latest step automatically.
  * on a real fleet, a failed host triggers a restart from the latest
    checkpoint on the surviving mesh (see train/elastic.py for the
    reshard-on-restore path, exercised in tests by mesh-shape changes).
  * resume picks the newest checkpoint that passes ``verify()``
    (``latest_valid_step``), so a corrupt/truncated newest checkpoint
    costs one interval, not the run.
  * non-finite loss/grad-norm steps apply NO update (``skip_nonfinite``;
    counted in ``skipped_steps``); ``rollback_after`` consecutive bad
    steps trigger a rollback to the newest verified checkpoint (sampler
    mode).  See docs/ARCHITECTURE.md "Failure model".

Straggler mitigation (documented policy, host-side): per-step wall-time
is tracked with an EWMA; steps exceeding ``straggler_factor`` x EWMA are
counted and surfaced in metrics — on a fleet this signal feeds the
controller that evicts/replaces slow hosts.

Where the host time goes: the next batch is drawn only after the host
has read the step's ``ok`` flag (``bool(ok)``) and, with a sampler, its
loss (``float(l)``), so the draw waits for the step instead of
overlapping it.  Each step opens profiler spans (``repro.spans``):
``StepTraceAnnotation("train")`` around the iteration, ``train/dispatch``
around the step's dispatch (with the model's loss, ``attn=`` names its
attention path, ``"flash"`` or ``"chunked"``), ``train/sync`` around
each read of its results and ``train/draw`` around each batch draw (with
the sampler's own ``lgd/...`` and ``index/...`` spans inside).
``span_totals()`` sums their seconds and calls without a device sync;
``data_seconds`` is the ``train/draw`` total.

ADAPTIVE OPTIMIZERS under LGD: the sampler composes with ANY
``repro.optim`` optimiser (Adam, AdaGrad, momentum-SGD, ...) because
the importance weights enter the LOSS, not the update rule: the jitted
loss multiplies per-example losses by 1/(p_i N), so the gradient the
optimiser receives IS the unbiased estimate of the full-batch gradient
— Adam's first/second moments and AdaGrad's accumulator are then
running statistics OF that estimate (weights applied strictly before
moment accumulation).  First moments therefore track the true mean
gradient: E[m_1] = (1-b1) * full-batch grad (pinned by
tests/test_optim_lgd.py against full-batch moments).  Second moments
accumulate E[g_est^2] >= E[g_est]^2 — the correct Adam/AdaGrad
semantics for any stochastic estimator; nothing in the update rule
needs to know the batch was adaptively sampled.

LGD sampler hook: pass ``sampler=`` (an ``LSHSampledPipeline`` /
``ShardedLSHPipeline``) instead of ``batches``.  The trainer then
  * draws batches from ``sampler.next_batch`` — importance weights
    1/(p_i N) ride in ``batch["loss_weights"]`` and are applied INSIDE
    the jitted loss (``models.layers.chunked_cross_entropy``), keeping
    the adaptive-sampling gradient unbiased.  Batches arrive as DEVICE
    arrays (the pipeline's sample->gather->weight program runs on
    device against its resident token store), so drawing costs only the
    dispatch of one compiled call — there is no host-side batch
    assembly or re-upload anywhere in the loop;
  * pushes fresh params via ``sampler.set_params`` after every step, so
    queries track the live model and the periodic index refresh (which
    the pipeline runs on a host thread, double-buffered) re-embeds from
    near-current params while the device step runs;
  * forces ``donate=False`` (the sampler's feature/query closures read
    live param buffers) and, on restore, rewinds the sampler with
    ``restore_at(step)`` instead of replaying consumed batches.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation

from repro.models import ModelConfig, loss as lm_loss
from repro.models.lm import forward_attention
from repro.optim import apply_updates
from repro.spans import Spans, Totals, merged
from . import checkpoint as ckpt


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    keep_ckpts: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    grad_clip: Optional[float] = 1.0
    # donate params/opt_state buffers to the step (halves peak HBM).
    # Disable when an LGD pipeline holds references to live params
    # (its feature/query closures would read donated buffers).
    donate: bool = True
    # micro-batching: split each batch into N equal slices along dim 0 and
    # accumulate gradients — decouples the optimisation batch size from
    # per-device memory (used by elastic rescale to keep global batch
    # fixed when devices shrink).
    grad_accum: int = 1
    # int8 gradient compression with error feedback on the DP all-reduce
    # path (see optim/compression.py); quantisation happens inside the
    # step so the wire-crossing tree is 4x smaller than bf16.
    grad_compress: bool = False
    # -- self-healing guards (docs/ARCHITECTURE.md: failure model) --
    # a step whose loss or grad-norm is non-finite applies NO update
    # (params/opt_state/ef_residual selected unchanged inside the jitted
    # step); the batch is still consumed and ``step`` still advances, so
    # the data stream stays aligned with the step counter and restore
    # determinism holds.  Counted in ``skipped_steps``.
    skip_nonfinite: bool = True
    # after this many CONSECUTIVE skipped steps, roll back to the newest
    # checkpoint that passes verify() (sampler mode only — a plain batch
    # iterator cannot be rewound).  0 disables rollback.
    rollback_after: int = 5
    # lifetime cap on rollbacks (a persistent NaN source must not pin
    # the run in a restore loop forever).
    max_rollbacks: int = 3
    # called with the Trainer after every COMPLETED step (post-update,
    # post-checkpoint) — the multi-host deployment's attachment point
    # for heartbeats, membership barriers and cross-process parameter
    # averaging (repro.dist.multihost).  The hook may mutate
    # ``trainer.params`` (push the result via ``trainer.sampler
    # .set_params`` too) and may raise to unwind ``run()`` at a clean
    # step boundary: params/opt_state/step are consistent, and a later
    # ``restore_at`` realigns the data stream.
    step_hook: Optional[Callable] = None


class Trainer:
    """Training loop with LGD-sampler, checkpoint and metrics hooks.

    Args:
      cfg: model config (defines the default LM loss).
      params: initial parameter pytree.
      optimizer: any ``repro.optim`` optimiser (``init``/``update``
        interface) — Adam, AdaGrad, momentum-SGD, Adafactor, ...;
        with ``sampler=`` the importance-weighted gradient estimate
        feeds its moment accumulators unchanged (module docstring).
      batches: iterator of batch dicts (uniform-sampling mode);
        mutually exclusive with ``sampler``.
      tcfg: loop policy knobs (checkpointing, clipping, accumulation).
      resume: auto-restore the latest checkpoint in ``tcfg.ckpt_dir``.
      loss_fn: optional ``loss_fn(params, batch)`` override.
      sampler: an ``LSHSampledPipeline``/``ShardedLSHPipeline`` — the
        LGD adaptive-sampling mode (forces ``donate=False``; pushes
        live params via ``set_params`` each step; ``restore_at`` on
        checkpoint restore).

    Determinism: with a sampler, restoring at step t replays the exact
    batch sequence of a run that reached step t (fold_in key streams —
    see ``repro.data.lsh_pipeline``); with ``batches``, restore skips
    already-consumed batches, so the iterator must be re-creatable.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        optimizer,
        batches: Optional[Iterator[Dict[str, jax.Array]]] = None,
        tcfg: TrainerConfig = TrainerConfig(),
        resume: bool = True,
        loss_fn: Optional[Callable] = None,
        sampler=None,
    ):
        if (batches is None) == (sampler is None):
            raise ValueError("pass exactly one of batches= or sampler=")
        self._sampler = sampler
        if sampler is not None:
            if hasattr(sampler, "set_params"):
                sampler.set_params(params)
            batches = iter(sampler.next_batch, None)
            if tcfg.donate:
                # sampler closures read live param buffers; donating
                # them to the step would hand the sampler freed memory.
                tcfg = dataclasses.replace(tcfg, donate=False)
        self.cfg = cfg
        self.optimizer = optimizer
        self.batches = batches
        self.tcfg = tcfg
        self.params = params
        self.opt_state = optimizer.init(params)
        self.step = 0
        self.metrics_history = []
        self._ckpt = ckpt.AsyncCheckpointer()
        self._ewma_dt = None
        self.straggler_steps = 0
        self.skipped_steps = 0      # non-finite steps (no update applied)
        self.rollbacks = 0          # checkpoint rollbacks taken
        self._bad_streak = 0        # consecutive skipped steps
        self.spans = Spans()        # train/... host spans (module docstring)
        # the model's own loss: its dispatch span names the attention path
        self._model_loss = loss_fn is None
        loss_fn = loss_fn or (lambda p, b: lm_loss(p, cfg, b))

        clip = tcfg.grad_clip
        accum = max(tcfg.grad_accum, 1)
        compress_on = tcfg.grad_compress
        if compress_on:
            from repro.optim import compression as _gc
            self._ef_residual = _gc.init_error_feedback(params)

        def grads_of(params, batch):
            if accum == 1:
                return jax.value_and_grad(loss_fn)(params, batch)

            def micro(i):
                mb = jax.tree.map(
                    lambda x: x.reshape(
                        (accum, x.shape[0] // accum) + x.shape[1:])[i]
                    if hasattr(x, "shape") and x.ndim >= 1 else x, batch)
                return jax.value_and_grad(loss_fn)(params, mb)

            def body(carry, i):
                l_acc, g_acc = carry
                l, g = micro(i)
                return (l_acc + l,
                        jax.tree.map(jnp.add, g_acc, g)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (l, g), _ = jax.lax.scan(
                body, (jnp.zeros(()), zeros), jnp.arange(accum))
            scale = 1.0 / accum
            return l * scale, jax.tree.map(lambda x: x * scale, g)

        guard = tcfg.skip_nonfinite

        def train_step(params, opt_state, batch, ef_residual=None):
            l, grads = grads_of(params, batch)
            old_ef = ef_residual
            if compress_on:
                from repro.optim import compression as _gc
                # this quantised tree is what crosses the DP links
                qtree, ef_residual = _gc.compress_with_feedback(
                    grads, ef_residual)
                grads = _gc.decompress(qtree, like=grads)
            if clip is not None or guard:
                # a single NaN/Inf anywhere in the gradient tree
                # propagates into this norm, so isfinite(gnorm) is a
                # whole-tree finiteness check.
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)))
            else:
                gnorm = jnp.zeros(())
            if clip is not None:
                scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
                grads = jax.tree.map(
                    lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                    grads)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = apply_updates(params, updates)
            if guard:
                # branchless skip: a non-finite loss or grad-norm keeps
                # params/opt_state/error-feedback EXACTLY as they were
                # (the where selects the old buffers) — the poisoned
                # gradients never reach the optimiser's moments.
                ok = jnp.isfinite(l) & jnp.isfinite(gnorm)
                sel = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                new_params = jax.tree.map(sel, new_params, params)
                new_opt = jax.tree.map(sel, new_opt, opt_state)
                if compress_on:
                    ef_residual = jax.tree.map(sel, ef_residual, old_ef)
            else:
                ok = jnp.array(True)
            return new_params, new_opt, l, gnorm, ef_residual, ok

        self._step_fn = jax.jit(
            train_step, donate_argnums=(0, 1) if tcfg.donate else ())

        if resume and tcfg.ckpt_dir:
            # resume from the newest checkpoint that passes verify() —
            # a corrupt/truncated newest checkpoint costs one interval,
            # not the run.
            last = ckpt.latest_valid_step(tcfg.ckpt_dir)
            if last is not None:
                self.restore(last)

    # -- checkpoint ----------------------------------------------------------

    def _state_tree(self):
        return {"params": self.params, "opt_state": self.opt_state}

    def save(self):
        if not self.tcfg.ckpt_dir:
            return
        extra = {"step": self.step}
        if self._sampler is not None and \
                getattr(self._sampler, "streaming", False) and \
                hasattr(self._sampler, "mutation_log"):
            # streaming pipelines: the explicit append/evict log rides
            # in the manifest so a restore can replay membership and
            # keep restored-at-step bit-determinism (lsh_pipeline
            # module docstring, STREAMING CORPORA).
            extra["mutation_log"] = self._sampler.mutation_log()
        self._ckpt.save(
            self.tcfg.ckpt_dir, self.step, self._state_tree(),
            extra=extra)
        ckpt.keep_last(self.tcfg.ckpt_dir, self.tcfg.keep_ckpts)

    def restore(self, step: int):
        tmpl = self._state_tree()
        tree, extra = ckpt.restore(self.tcfg.ckpt_dir, step, tmpl)
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        self.step = extra.get("step", step)
        # checkpoints newer than the restore point are an abandoned
        # timeline (corrupt newest, or a rolled-back poisoned future) —
        # drop them so the resumed run's own writes are authoritative.
        ckpt.discard_after(self.tcfg.ckpt_dir, self.step)
        if self._sampler is not None and hasattr(self._sampler,
                                                 "restore_at"):
            # rebuild the sampler's index from the restored params and
            # rewind its key streams — O(refresh) instead of O(steps),
            # and bit-deterministic across restores.
            if hasattr(self._sampler, "set_params"):
                self._sampler.set_params(self.params)
            if "mutation_log" in extra and \
                    hasattr(self._sampler, "load_mutation_log"):
                # restore the streaming membership history first;
                # restore_at replays it before the canonical rebuild.
                self._sampler.load_mutation_log(extra["mutation_log"])
            self._sampler.restore_at(self.step)
        else:
            # deterministic data resume: skip already-consumed batches
            for i in range(self.step):
                try:
                    next(self.batches)
                except StopIteration:
                    raise RuntimeError(
                        f"batch iterator exhausted after {i} batches "
                        f"while skipping to checkpoint step {self.step} "
                        f"— the iterator is shorter than the checkpoint "
                        f"(it must be re-creatable past the restore "
                        f"point)") from None

    def _rollback(self) -> bool:
        """Roll back to the newest VERIFIED checkpoint after a streak of
        non-finite steps (sampler mode only — ``restore_at`` rewinds the
        data stream; a plain iterator cannot).  Returns True on success.
        """
        try:
            self._ckpt.wait()           # surface a boxed async failure
        except RuntimeError:
            pass                        # the write failed; disk may still
            #                             hold an older valid checkpoint
        step_v = ckpt.latest_valid_step(self.tcfg.ckpt_dir)
        if step_v is None:
            return False
        prev = self.step
        self.restore(step_v)
        self.rollbacks += 1
        self._bad_streak = 0
        self.metrics_history.append({
            "step": self.step, "event": "rollback",
            "from_step": prev, "to_step": step_v,
            "skipped_steps": self.skipped_steps,
        })
        return True

    def finalize(self):
        self._ckpt.wait()
        if self._sampler is not None and hasattr(self._sampler, "finalize"):
            self._sampler.finalize()

    # -- loop ----------------------------------------------------------------

    @property
    def sampler(self):
        """The LGD sampler this trainer drives (None in batches mode) —
        exposed for step hooks that mutate params and must push them."""
        return self._sampler

    @property
    def data_seconds(self) -> float:
        """Host time spent blocked on batch draws (``train/draw``)."""
        return self.spans.seconds("train/draw")

    def span_totals(self) -> Totals:
        """``{name: (seconds, calls)}`` of the trainer's spans and its
        sampler's, summed over the run so far (no device sync)."""
        own = self.spans.totals()
        if self._sampler is not None and \
                hasattr(self._sampler, "span_totals"):
            return merged(own, self._sampler.span_totals())
        return own

    def _draw(self, step: int):
        """The batch that trains step ``step`` (0-based)."""
        with self.spans("train/draw", step=step):
            return next(self.batches)

    def run(self, n_steps: int) -> Dict[str, list]:
        losses = []
        if n_steps <= 0:
            # never touch the data stream: batch k must train step k,
            # and a no-op run() must not tick the sampler's key stream.
            return {"losses": losses}
        target = self.step + n_steps
        try:
            next_batch = self._draw(self.step)
        except StopIteration:
            # an empty/exhausted iterator on the FIRST draw is a clean
            # no-op run, not a crash (satellite: bare StopIteration).
            return {"losses": losses}
        while self.step < target:
            with StepTraceAnnotation("train", step_num=self.step):
                t0 = time.perf_counter()
                attn = ({"attn": forward_attention(
                    self.params, self.cfg, next_batch)}
                    if self._model_loss else {})
                with self.spans("train/dispatch", step=self.step, **attn):
                    self.params, self.opt_state, l, gnorm, ef, ok = \
                        self._step_fn(self.params, self.opt_state,
                                      next_batch,
                                      getattr(self, "_ef_residual", None))
                if ef is not None:
                    self._ef_residual = ef
                if self.tcfg.skip_nonfinite:
                    with self.spans("train/sync", step=self.step):
                        ok = bool(ok)
                else:
                    ok = True
                if ok:
                    self._bad_streak = 0
                else:
                    self.skipped_steps += 1
                    self._bad_streak += 1
                if self._sampler is not None and \
                        hasattr(self._sampler, "note_loss"):
                    # feed the degradation ladder: a non-finite streak sends
                    # the pipeline to uniform-fallback (weights un-poisoned
                    # by construction).
                    self._sampler.note_loss(ok)
                if not ok and self.tcfg.rollback_after > 0 and \
                        self._bad_streak >= self.tcfg.rollback_after and \
                        self._sampler is not None and self.tcfg.ckpt_dir and \
                        self.rollbacks < self.tcfg.max_rollbacks:
                    if self._rollback():
                        # the prefetched batch belongs to the abandoned
                        # stream position; re-draw at the rolled-back step.
                        next_batch = self._draw(self.step)
                        continue
                if self._sampler is not None and \
                        hasattr(self._sampler, "set_params"):
                    # point the sampler at the post-step params (async jax
                    # values — sampling ops just enqueue behind the step)
                    # BEFORE drawing the next batch, so its query reflects
                    # the live model.
                    self._sampler.set_params(self.params)
                    # the draw's query depends on the step's output params,
                    # and dispatching on a pending input blocks on backends
                    # without cross-dependency async (CPU) — sync the loss
                    # first so data_seconds measures the DRAW, not the
                    # in-flight step it would otherwise absorb.
                    with self.spans("train/sync", step=self.step):
                        l = float(l)
                if self.step + 1 < target:
                    # prefetch ONLY if another step will run: batch k must
                    # train step k, never be thrown away at loop exit —
                    # otherwise chunked run() calls desync the data stream
                    # from self.step and restore-at-step resume diverges.
                    try:
                        next_batch = self._draw(self.step + 1)
                    except StopIteration:
                        next_batch = None
                else:
                    next_batch = None
                if not isinstance(l, float):
                    with self.spans("train/sync", step=self.step):
                        l = float(l)
                dt = time.perf_counter() - t0
                self._ewma_dt = dt if self._ewma_dt is None else \
                    0.9 * self._ewma_dt + 0.1 * dt
                if dt > self.tcfg.straggler_factor * self._ewma_dt:
                    self.straggler_steps += 1
                self.step += 1
                losses.append(l)
                if self.step % self.tcfg.log_every == 0:
                    entry = {
                        "step": self.step, "loss": l,
                        "grad_norm": float(gnorm), "dt": dt,
                        "stragglers": self.straggler_steps,
                        "skipped_steps": self.skipped_steps,
                        "rollbacks": self.rollbacks,
                    }
                    if self._sampler is not None and \
                            hasattr(self._sampler, "sampler_stats"):
                        # device-sync'd read, so only at log cadence
                        st = self._sampler.sampler_stats()
                        entry["fallback_rate"] = st["fallback_rate"]
                        entry["primary_miss_rate"] = st["primary_miss_rate"]
                    if self._sampler is not None and \
                            hasattr(self._sampler, "check_health"):
                        # feeds the batch fallback rate into the ladder and
                        # reports the state (syncs; log cadence only)
                        entry["health"] = self._sampler.check_health()
                        hs = self._sampler.health_summary()
                        entry["health_transitions"] = hs["transitions"]
                    self.metrics_history.append(entry)
                if self.tcfg.ckpt_dir and \
                        self.step % self.tcfg.ckpt_every == 0:
                    self.save()
                if self.tcfg.step_hook is not None:
                    # cluster attachment point — may mutate params or raise
                    # (e.g. HostLossDetected) at this clean step boundary.
                    self.tcfg.step_hook(self)
                if next_batch is None:
                    break
        return {"losses": losses}
