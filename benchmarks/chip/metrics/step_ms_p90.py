"""90th percentile of the window's step times, each from the trainer's
step hook (the host boundary after the step's loss is read)."""

import numpy as np


def read(run):
    return float(np.percentile(run.step_s, 90)) * 1e3
