"""Device time per step of the ops under the program's ``mamba2`` scope:
the Mamba-2 mixers (pre-norm, in_proj, conv, SSD, gated norm, out_proj),
forward, recomputation and backward, in the traced window."""

from chipbench import scopes


def read(run):
    s = scopes.window_seconds(run, "mamba2")
    return None if not s else 1e3 * s / run.steps
