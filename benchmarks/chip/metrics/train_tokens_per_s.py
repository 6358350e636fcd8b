"""Tokens trained in the window over its wall time (host clock), draws,
refreshes and host syncs included."""


def read(run):
    return run.tokens / run.window_s
