"""Device time per step of the ops under the program's ``mamba2/ssd``
scope: the Mamba-2 state-space core (the split of x, B and C, dt and the
decays, the chunked scan and the D skip), forward, recomputation and
backward, in the traced window."""

from chipbench import scopes


def read(run):
    s = scopes.window_seconds(run, "mamba2/ssd")
    return None if not s else 1e3 * s / run.steps
