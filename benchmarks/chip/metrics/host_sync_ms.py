"""Host time the trainer spent blocked on its step's results (the
program's ``train/sync`` spans around ``bool(ok)`` and ``float(loss)``)
per step of the traced window."""

from chipbench import program_spans


def read(run):
    r = program_spans.window(run)
    if r is None or "train/sync" not in r.totals:
        return None
    return 1e3 * program_spans.seconds(r, "train/sync") / run.steps
