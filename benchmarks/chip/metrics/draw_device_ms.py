"""Device time of the draw program (``jit_sample_gather``: query hash,
bucket probe, in-bucket draw, row gather and weights) per step."""


def read(run):
    s = run.trace.module_s.get("jit_sample_gather")
    return None if s is None else 1e3 * s / run.steps
