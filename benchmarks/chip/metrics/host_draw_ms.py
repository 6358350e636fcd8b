"""Host time the trainer spent blocked on drawing batches
(``Trainer.data_seconds`` over the window) per step."""


def read(run):
    return 1e3 * run.host_draw_s / run.steps
