"""Smoke-size cells for the benchmark's CPU tests.

The configuration is Granite-3-8B's layout at the program's SMOKE widths
in float32, so a bfloat16 reference is the control one step below it.
The limits are set from CPU readings of the LGD cell over seeds 0-5: the
program's gaps all lie under 2e-6 (codes: none differ), the bfloat16
control's at or above 9e-5 in every gap but the codes (0 to 4 bits), with room on both
sides.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import arch, spec  # noqa: E402

CONFIG = {
    "model_type": "granite", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2,
    "vocab_size": 128, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_theta": 500000.0, "torch_dtype": "float32",
    "execution": {"attn_block_q": 16, "loss_chunk": 16},
}
_COMMON = {"seq": 32, "batch": 2, "corpus_rows": 64, "zipf_exponent": 1.1,
           "hard_frac": 0.1, "lr": 1e-3, "warmup_steps": 10,
           "schedule_steps": 1000}
TRAFFIC = {
    "lgd": {**_COMMON, "sampler": "lgd", "k": 7, "l": 10, "multiprobe": 28,
            "refresh_every": 4, "refresh_lead": 1},
    "uniform": {**_COMMON, "sampler": "uniform"},
}
LIMITS = {"loss_gap": 3e-5, "grad_gap": 3e-5, "change_gap": 3e-5,
          "rows_mismatch": 0, "weight_gap": 3e-5, "caught_errors": 0,
          "code_mismatch": 0, "feature_gap": 3e-5}
METRICS = [{"name": n, "unit": "x"} for n in (
    "train_tokens_per_s", "step_ms_p90", "setup_s")]


def cell(kind):
    limits = dict(LIMITS)
    if kind == "uniform":
        for k in ("weight_gap", "caught_errors", "code_mismatch",
                  "feature_gap"):
            limits.pop(k)
    return spec.Cell(name=f"smoke-{kind}", chips=1, config_name="smoke",
                     config=CONFIG, arch=arch.load("granite"),
                     traffic_name=kind, traffic=TRAFFIC[kind],
                     end_to_end=METRICS,
                     per_layer=[], limits=limits)
