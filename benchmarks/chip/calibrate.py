"""Readings that a cell's correctness limits are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11 12 13 ... [--control-seeds 11 12 13]

For each seed, in one process on the chip: build the cell at its own size,
drive it through its warm-up (no measured window), and print one JSON line
with the numbers ``correct`` compares (the program against the reference).
For each control seed, also print the same numbers with the reference at
the next lower precision in the program's place (float8 for a bfloat16
configuration, bfloat16 for a float32 one).  The limit of each number lies
between the largest program reading and the smallest control reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LOWER = {"bfloat16": "float8", "float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

    import jax
    from chipbench import run, spec
    from repro.dist.sharding import use_mesh
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    cfg = spec.model_config(cell.config, cell.config_name)
    control = LOWER[cfg.dtype]
    mesh = make_host_mesh()
    for seed in args.seeds:
        with use_mesh(mesh):
            tr, prog, _ = run.set_up(cell, seed, mesh)
            run.close(tr, prog)
            del tr
            sides = [("program", None)] + (
                [(control, control)] if seed in args.control_seeds else [])
            for side, precision in sides:
                detail = {}
                nums = run.reference_numbers(cell, cfg, seed, prog, precision,
                                             detail)
                print(json.dumps({"workload": cell.name, "seed": seed,
                                  "side": side, **nums, "detail": detail}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
