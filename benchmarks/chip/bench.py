"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs on the machine it is started on, from the root of a checkout, and
needs as many TPU chips as the cell names; without them it exits non-zero
and prints no result.  It builds the cell's trainer from the seed, warms
up every program the window drives, measures for ``--seconds`` (whole
refresh periods where the cell refreshes), checks the timed path against
the plain reference, and prints one JSON line last on stdout.  With
``--trace 1`` the window is profiled and the line carries the cell's
per-layer metrics instead of its end-to-end ones.

JAX's compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR`` if set,
else in ``<checkout>/.jax_cache``; a traced run's profile is kept in
``<checkout>/.bench_trace`` until the next traced run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def fail(msg, code=1):
    print(f"error: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"no program under {src}: run from a checkout of the repo")
    sys.path[:0] = [src, HERE]
    from chipbench import spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX's first device is {devices[0].platform!r}, "
                    f"not a TPU")
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from chipbench.run import run_cell
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, TRACE_DIR)
    except Exception:
        traceback.print_exc()
        return fail(f"{cell.name} seed {args.seed} did not complete")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
