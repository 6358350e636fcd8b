"""Device time of the index refresh per step: the embedding pass
(``jit_refresh_embed``), the re-hash (``jit_simhash_codes``) and the
re-sort (``jit_argsort``), over the window's steps."""

PROGRAMS = ("jit_refresh_embed", "jit_simhash_codes", "jit_argsort")


def read(run):
    m = run.trace.module_s
    if "jit_refresh_embed" not in m:
        return None
    return 1e3 * sum(m.get(p, 0.0) for p in PROGRAMS) / run.steps
