"""The fused spectral-mix kernel's share of its roofline: the least time
the chip could take for the mix's required work (``models/<family>.
mix_work``: the multiply-adds of the kept modes, one read of the weights
and of the kept coefficients, one write of the result) over the device
time of the kernel's events in the trace: the ``custom-call`` ops named
after the kernel (``spectral_fused``). Each event is one block's mix at
the bucket's batch; a chip of a model-parallel mesh does its share of the
modes. No such event: no number. %."""
KERNEL = "spectral_fused"


def is_kernel(op):
    return op.category == "custom-call" and op.name.split(".")[0] == KERNEL


def read(run):
    t, p = run.trace, run.peaks
    ops, nbytes = run.model.mix_work(run.config, run.counts["bucket"])
    least = max(ops / p.flops_bf16, nbytes / p.hbm_bandwidth) / run.chips
    events = [o for d in range(len(t.devices)) for o in t.ops(d, is_kernel)]
    if not events:
        return None
    busy = sum(o.end - o.start for o in events) * 1e-9
    return 100.0 * len(events) * least / busy
