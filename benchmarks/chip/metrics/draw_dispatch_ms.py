"""Host time of the batch draw less its wait at the refresh swap (the
program's ``lgd/draw`` spans less its ``index/refresh_join`` spans: query,
draw dispatch, its eager side ops and the batch composition) per step of
the traced window."""

from chipbench import program_spans


def read(run):
    r = program_spans.window(run)
    if r is None or "lgd/draw" not in r.totals:
        return None
    return 1e3 * (program_spans.seconds(r, "lgd/draw")
                  - program_spans.seconds(r, "index/refresh_join")
                  ) / run.steps
