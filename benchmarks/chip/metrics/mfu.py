"""Model FLOPs of the tokens trained in the window (6 per matmul parameter
per token plus causal attention; no recomputation) over window x chips x
the chip's bf16 peak."""

from chipbench import flops


def read(run):
    per_step = flops.train_flops_per_step(
        run.cfg, run.traffic["batch"], run.traffic["seq"])
    peak = flops.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * per_step * run.steps / (
        run.window_s * run.chips * peak)
