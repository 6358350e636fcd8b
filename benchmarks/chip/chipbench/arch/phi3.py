"""Phi-3's decoder (Phi-4-mini) as the program runs it: Granite's dense
stack.  The program reads no partial rotary factor and no rope scaling, so
a file may give them only at full rotary and ``null``; at ``null`` the
``*max_position_embeddings`` keys change nothing the program computes.
"""

from __future__ import annotations

from . import granite
from .granite import (  # noqa: F401
    constants, hidden, make_params, matmul_params, model_config,
    train_flops_per_step)

KEYS = (*granite.KEYS, "original_max_position_embeddings")
FIXED = {**granite.FIXED, "partial_rotary_factor": 1.0}
