"""Device time of the trainer's step program (``jit_train_step``) per step."""


def read(run):
    s = run.trace.module_s.get("jit_train_step")
    return None if s is None else 1e3 * s / run.steps
