"""Model FLOPs of the tokens trained in the window (the count of the cell's
architecture module, ``chipbench/arch/<model_type>.py``; no recomputation)
over window x chips x the chip's bf16 peak."""

from chipbench import flops


def read(run):
    per_step = run.cell.arch.train_flops_per_step(
        run.cfg, run.traffic["batch"], run.traffic["seq"])
    peak = flops.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * per_step * run.steps / (
        run.window_s * run.chips * peak)
