"""Device idle time inside the program's ``fno_runner.forward`` span, the
jitted forward from its call on host arrays until its output is a host
array: the span's length minus the device's busy time in it (the union of
its op intervals), mean over the chips used; median over the window's
ticks. What is left is upload, download and dispatch that no device op
overlaps. ms."""
import statistics

from harness import program


def _busy_in(intervals, a, b):
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in intervals)


def read(run):
    spans = program.records(run, "fno_runner.forward")
    if not spans:
        return None
    t = run.trace
    busy = [t.busy_intervals(d) for d in range(len(t.devices))]
    idle = []
    for r in spans:
        a, b = program.to_device(run, r.start), program.to_device(run, r.end)
        idle.append(sum(b - a - _busy_in(iv, a, b) for iv in busy) / len(busy))
    return 1e-6 * statistics.median(idle)
