"""Model FLOPs of the train steps completed in the window (forward and
backward, three forwards per sample; recomputation not counted) over the
window and the chips' bf16 peak. %."""


def read(run):
    c = run.counts
    flops = run.model.train_step_flops(run.config, c["batch"]) * c["steps"]
    return 100.0 * flops / c["window_s"] / (run.chips * run.peaks.flops_bf16)
