"""Device time per step of the ops under the program's ``attention`` scope
inside the trainer's step program (tf_op paths under ``jit(train_step)``):
the self-attention core between the q/k/v projections and ``wo`` (the flash
kernel and its backward, or the chunked scan), forward, recomputation and
backward, in the traced window.  The refresh embed's attention, in
``jit(refresh_embed)``, is left out."""

from chipbench import scopes

PROGRAM = ("train_step",)


def read(run):
    if not scopes.window_seconds(run, "attention"):
        return None
    planes = [(marks, [op for op in ops if op[0][:1] == PROGRAM])
              for marks, ops in run.scoped_ops]
    s = scopes.scope_seconds(planes, "attention")
    return None if not s else 1e3 * s / run.steps
