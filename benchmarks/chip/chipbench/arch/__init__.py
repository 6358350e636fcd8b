"""One module per architecture: ``chipbench/arch/<model_type>.py``.

A configuration file's ``model_type`` names the module that builds, draws,
checks and counts it, so a new architecture is a new module and no edit
here or elsewhere in the harness.  Each module gives:

- ``model_config(conf, name)``: the program's ``ModelConfig`` for the file;
- ``KEYS``: the file's keys it takes at any value (those ``model_config``
  reads, and any that change nothing the program computes);
- ``FIXED``: the keys the program does not read, each with the only value
  at which the program computes what the key says (a value, or a function
  of the ``ModelConfig`` giving it);
- ``make_params(key, cfg)``: the program's parameter layout, drawn from
  ``key`` independently of the program;
- ``constants(cfg)`` and ``hidden(params, tokens, c, precision)``: the
  plain float32 reference forward up to the final hidden state (before the
  final norm), written with ``reference.mm`` and ``reference.rms``;
- ``matmul_params(cfg)`` and ``train_flops_per_step(cfg, batch, seq)``:
  the model FLOPs of one training step.

``spec.model_config`` holds every configuration file to ``KEYS``,
``FIXED`` and the keys that only describe it.
"""

from __future__ import annotations

import importlib


def load(model_type):
    """The module of ``model_type``; without one, an error names the file
    to add."""
    if not (isinstance(model_type, str) and model_type.isidentifier()):
        raise ValueError(f"model_type {model_type!r} cannot name a module "
                         f"under chipbench/arch/")
    name = f"{__name__}.{model_type}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
    raise ValueError(f"no module for model_type {model_type!r}: add "
                     f"chipbench/arch/{model_type}.py")
