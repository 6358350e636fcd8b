"""Host time the draw waited at the index refresh's swap (the program's
``index/refresh_join`` spans: joining the refresh worker through the
commit) per step of the traced window."""

from chipbench import program_spans


def read(run):
    r = program_spans.window(run)
    if r is None or "lgd/draw" not in r.totals:
        return None
    return 1e3 * program_spans.seconds(r, "index/refresh_join") / run.steps
