"""Process start to the first timed step: imports, inputs and weights,
index build, compilation or cache loads, and the warm-up steps."""


def read(run):
    return run.setup_s
