"""Model FLOPs of the rollout steps served in the window over the window
and the chips' bf16 peak. Each served rollout step counts one full forward
of its sample (``models/<family>.forward_flops``), whether its geomodel
prefix came from the cache or not; padding slots do not count. %."""


def read(run):
    c = run.counts
    flops = run.model.forward_flops(run.config)["total"] * c["rollout_steps"]
    return 100.0 * flops / c["window_s"] / (run.chips * run.peaks.flops_bf16)
