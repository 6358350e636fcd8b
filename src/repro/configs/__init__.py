"""Architecture registry: one module per assigned architecture.

``get(name)`` returns the FULL production config; ``get_smoke(name)``
the reduced same-family config used by CPU smoke tests.  FULL configs
are only ever lowered via ShapeDtypeStructs (launch/dryrun.py) — never
allocated on the CPU host.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models import ModelConfig

ARCHS: List[str] = [
    "xlstm_350m",
    "qwen3_moe_235b_a22b",
    "llama4_maverick_400b_a17b",
    "phi4_mini_3_8b",
    "granite_3_8b",
    "starcoder2_15b",
    "nemotron_4_15b",
    "musicgen_large",
    "llama_3_2_vision_90b",
    "zamba2_1_2b",
    "granite_4_0_h_micro",
]

# accepted CLI aliases (--arch with dashes/dots)
def _canon(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def get(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_canon(name)}")
    return mod.FULL


def get_smoke(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_canon(name)}")
    return mod.SMOKE


def all_archs() -> List[str]:
    return list(ARCHS)
