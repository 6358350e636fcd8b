"""Quickstart: LGD (LSH-sampled gradient descent) vs plain SGD on least squares.

Reproduces the paper's core experiment on CPU:
  1. build hash tables over [x_i, y_i]  (one-time cost)
  2. per step: hash-lookup sample -> unbiased gradient -> optimiser update
  3. compare convergence against uniform-sampling SGD

The gradient ESTIMATOR is what LGD replaces, so any first-order
optimiser plugs in underneath (``--optimizer {sgd,momentum,adagrad,
adam}``), and ``--multiprobe`` turns on Hamming-ball multi-probe
querying (empty buckets resolve to probability-corrected neighbour
buckets instead of uniform fallbacks).

The hash family is pluggable (``--family {quadratic,srp,mips}``):
``quadratic`` (default) matches |<q,x>| exactly via the implicit
squared expansion; ``srp`` is plain cosine SimHash on the normalised
rows; ``mips`` demonstrates the asymmetric Simple-LSH family — the
same data WITHOUT the unit-norm preprocessing restriction, hashed
through the [x/M, sqrt(1-||x/M||^2)] augmentation.

Run:  PYTHONPATH=src python examples/quickstart.py [--steps 600]
          [--optimizer sgd] [--multiprobe 2] [--family mips]
"""

import argparse

import jax

from repro.core import (
    LGDProblem, LSHParams, full_loss, get_family, init, lgd_step,
    sgd_step,
)
from repro.data import make_regression
from repro.optim import make_optimizer
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600,
                    help="training steps (600 reproduces the paper curve; "
                         "use ~60 for a smoke run)")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adagrad", "adam"],
                    help="optimiser under BOTH estimators (LGD only "
                         "replaces the gradient estimate)")
    ap.add_argument("--multiprobe", type=int, default=0,
                    help="extra Hamming-ball probe codes per table")
    ap.add_argument("--family", default="quadratic",
                    choices=["quadratic", "srp", "mips", "mips_banded"],
                    help="LSH family (core.families registry): quadratic "
                         "matches |<q,x>|; srp is cosine SimHash; mips is "
                         "the asymmetric no-normalisation Simple-LSH; "
                         "mips_banded adds norm-ranged banding for "
                         "heavy-tailed norms")
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    ds = make_regression(key, "yearmsd-like", n_train=8000, d=90,
                         noise="pareto")
    # augmented-vector dim: the family owns it ([x, y] is d+1 = 91;
    # asymmetric families append their extra coordinates on top)
    dim = get_family(args.family).aug_dim(91)
    problem = LGDProblem(
        kind="regression",
        lsh=LSHParams(k=5, l=100, dim=dim, family=args.family),
        minibatch=16,
        multiprobe=args.multiprobe,
        # the MIPS families train on UN-normalised rows: bound the
        # rare tiny-p draws
        p_floor=1e-7 if args.family in ("mips", "mips_banded") else 0.0,
    )
    lr = 5e-2 if args.optimizer != "adam" else 5e-3
    if args.family in ("mips", "mips_banded"):
        # un-normalised rows: ||x_i||^2 ~ d instead of 1, so the
        # quadratic loss curvature (and the stable LR) scales by ~1/d
        lr /= ds.x_train.shape[1]
    opt = make_optimizer(args.optimizer, lr)
    state, xt, yt, x_aug = init(key, problem, ds.x_train, ds.y_train, opt)
    print(f"dataset: {ds.x_train.shape}, hash tables: "
          f"{state.index.sorted_codes.shape} (K={problem.lsh.k}, "
          f"L={problem.lsh.l}), family: {args.family}, "
          f"optimizer: {args.optimizer}")

    s_lgd = s_sgd = state
    for step in range(args.steps + 1):
        k = jax.random.fold_in(key, step)
        s_lgd, m = lgd_step(k, s_lgd, xt, yt, x_aug, problem, opt)
        s_sgd, _ = sgd_step(k, s_sgd, xt, yt, problem, opt)
        if step % max(args.steps // 6, 1) == 0:
            print(f"step {step:4d}  "
                  f"LGD loss {float(full_loss(s_lgd.theta, xt, yt, problem)):.4f}  "
                  f"SGD loss {float(full_loss(s_sgd.theta, xt, yt, problem)):.4f}  "
                  f"(bucket={float(m['bucket_size_mean']):.0f}, "
                  f"probes={float(m['n_probes_mean']):.1f}, "
                  f"fallback={float(m['fallback_frac']):.2f})")


if __name__ == "__main__":
    main()
