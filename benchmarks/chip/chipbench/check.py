"""The numbers ``correct`` compares, each against its limit.

Training (every cell): the first three steps of the timed object against
the reference from the same weights and batches -- the worst relative gap
of the loss of the first two steps (``loss_gap``), of a leaf's
clipped-gradient norm at step one as Adam's first moment holds it
(``grad_gap``), and of a leaf's norm of change after three steps
(``change_gap``).  The third step's loss is not compared: it is taken after
the first update that moves the weights (the warm-up's learning rate is 0
at step one), which at these one-layer cuts raises the loss from about 11
to about 23, so it magnifies round-off in the update by two orders.  A leaf's gap is measured
against the larger of the reference's norm of that leaf and of the median
leaf; leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of ``change_gap``.

Draw (LGD cells): every drawn row must be the corpus row at its example id
(``rows_mismatch``, exact), and every importance weight must equal
1/(p N), normalised over the batch, with p recomputed plainly from
sign projections of the indexed features and the cosine collision law
(``weight_gap``).  ``caught_errors`` counts failures the pipeline caught.

Refresh (cells that refresh inside their warm-up): the code bits the
refresh stored against a plain sign projection of its features, counting
only projections further than ``ROUNDING_BAND`` from zero, where float32
cannot flip a sign (``code_mismatch``), and a seeded sample of its
features against the
reference's pooled forward from the parameters the refresh read
(``feature_gap``).

Plain recomputations run in float64 numpy; ``precision`` lowers their
inputs for the control.
"""

from __future__ import annotations

import numpy as np

CHANGE_FLOOR = 1e-3     # share of the median leaf's gradient norm
LOSS_STEPS = 2          # steps whose loss is compared
# |x . w| below which a float32 projection of a unit row over 4096 terms
# may carry the wrong sign: about twenty times its rounding
ROUNDING_BAND = 1e-6


def _lower(x, precision):
    x = np.asarray(x, np.float64)
    if precision == "float32":
        return x
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16 if precision == "bfloat16"
                    else np.float32).astype(np.float64)


def leaf_gap(prog, ref, keep=None):
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else keep
    floor = np.maximum(ref, np.median(ref[keep]))
    return float(np.max(np.abs(prog - ref)[keep] / floor[keep]))


def training_gaps(prog, ref):
    """prog/ref: dicts of ``losses``, ``grad_norms``, ``change_norms``."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g = np.asarray(ref["grad_norms"])
    moved = g >= CHANGE_FLOOR * np.median(g)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr)[:LOSS_STEPS]
                                 / np.abs(lr)[:LOSS_STEPS])),
        "grad_gap": leaf_gap(prog["grad_norms"], g),
        "change_gap": leaf_gap(prog["change_norms"], ref["change_norms"],
                               moved),
    }


def rows_mismatch(batches, corpus):
    """Drawn rows that are not the corpus row at their example id."""
    corpus = np.asarray(corpus)
    bad = 0
    for b in batches:
        rows = corpus[np.asarray(b["example_ids"])]
        bad += int(np.sum(np.any(rows[:, :-1] != b["tokens"], axis=1)
                          | np.any(rows[:, 1:] != b["targets"], axis=1)))
    return bad


def projections(x, proj, precision="float32"):
    return _lower(x, precision) @ _lower(proj, precision)


def plain_codes(x, proj, k, precision="float32"):
    """(L, N) packed sign codes: bit j of table t is x . proj[:, t K + j] >= 0."""
    z = projections(x, proj, precision)
    bits = (z >= 0).reshape(z.shape[0], -1, k).astype(np.uint64)
    return np.sum(bits << np.arange(k, dtype=np.uint64), axis=-1).T


def codes_by_row(sorted_codes, order):
    """The index's (L, N) codes laid back in row order."""
    sorted_codes, order = np.asarray(sorted_codes), np.asarray(order)
    out = np.empty(sorted_codes.shape, np.uint64)
    for t in range(sorted_codes.shape[0]):
        out[t, order[t]] = sorted_codes[t]
    return out


def probe_masks(k, n_codes):
    """Exact bucket, then flip-1, then flip-2 masks of a K-bit code."""
    masks = [0] + [1 << i for i in range(k)]
    masks += [(1 << i) | (1 << j) for i in range(k) for j in range(i + 1, k)]
    return masks[:n_codes]


def draw_probs(draw, state, precision="float32"):
    """Algorithm 1's probability of each drawn sample, recomputed plainly.

    ``draw``: the sampler's record of one draw (``indices``, ``n_probes``,
    ``probe_code``, ``bucket_sizes``, ``fallback``).  ``state``: the
    indexed ``features``, ``projections``, ``query``, ``k`` and
    ``multiprobe``.  A sample whose reported bucket is not a bucket of the
    plain codes that holds it gets probability nan.
    """
    k = state["k"]
    feats = _lower(state["features"], precision)
    query = _lower(state["query"], precision)
    codes = plain_codes(feats, state["projections"], k, precision)
    qcode = plain_codes(query[None], state["projections"], k, precision)[:, 0]
    masks = probe_masks(k, 1 + state["multiprobe"])
    pops = np.array([bin(m).count("1") for m in masks], np.float64)
    n = feats.shape[0]
    out = []
    for i, l, j, size, fb in zip(
            *(np.asarray(draw[f]) for f in (
                "indices", "n_probes", "probe_code", "bucket_sizes",
                "fallback"))):
        if fb:
            out.append(1.0 / n)
            continue
        target = qcode ^ np.uint64(masks[j])                     # (L,)
        holds = codes[:, i] == target
        counts = np.sum(codes == target[:, None], axis=1)
        if not np.any(holds & (counts == size)):
            out.append(np.nan)
            continue
        x = feats[i]
        cos = x @ query / (np.linalg.norm(x) * np.linalg.norm(query))
        cp = 1.0 - np.arccos(np.clip(cos, -1.0, 1.0)) / np.pi
        q_all = cp ** (k - pops) * (1.0 - cp) ** pops
        miss = max(1.0 - q_all.sum(), 0.0)
        out.append(q_all[j] * miss ** (l - 1) / size)
    return np.asarray(out)


def plain_weights(draw, state, precision="float32"):
    """The batch weights 1/(p N) of one draw, normalised to mean 1."""
    p = draw_probs(draw, {**state, "query": draw["query"]}, precision)
    w = 1.0 / np.maximum(p, state["p_floor"])
    return w / w.mean()


def weight_gap(weights, draws, state):
    """Worst relative gap of a batch weight from the plain 1/(p N)."""
    worst = 0.0
    for got, d in zip(weights, draws):
        w = plain_weights(d, state)
        if np.any(np.isnan(w)):
            return float("inf")
        got = np.asarray(got, np.float64)
        worst = max(worst, float(np.max(np.abs(got - w) / w)))
    return worst


def code_mismatch(stored, refresh):
    """Stored code bits ((L, N) codes) that differ from the plain sign
    projection where it lies outside the rounding band."""
    k = refresh["k"]
    z = projections(refresh["features"], refresh["projections"])
    n = z.shape[0]
    plain = (z >= 0).reshape(n, -1, k)
    got = (np.asarray(stored, np.uint64).T[..., None]
           >> np.arange(k, dtype=np.uint64)) & np.uint64(1)
    outside = np.abs(z).reshape(n, -1, k) > ROUNDING_BAND
    return int(np.sum((got.astype(bool) != plain) & outside))


def feature_row_gaps(prog_rows, ref_rows):
    """Distance of each stored feature from the normalised reference."""
    ref = np.asarray(ref_rows, np.float64)
    ref = ref / np.linalg.norm(ref, axis=1, keepdims=True)
    return [float(g) for g in np.linalg.norm(
        np.asarray(prog_rows, np.float64) - ref, axis=1)]


def feature_gap(prog_rows, ref_rows):
    """Worst distance between a stored feature and the normalised reference."""
    return max(feature_row_gaps(prog_rows, ref_rows))


def judge(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]) over the numbers this cell limits."""
    rows = [(k, numbers[k], limits[k]) for k in sorted(limits)]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok), rows
