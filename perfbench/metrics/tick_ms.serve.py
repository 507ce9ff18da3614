"""Median time of one ``Scheduler.step()`` (admit, one batched runner step
with its host staging and copies, retire), from the benchmark's own span
around each call in the window. Host clock, ms."""
import statistics


def read(run):
    ticks = run.spans.durations("tick")
    return 1e3 * statistics.median(ticks) if ticks else None
