"""Median wait in the scheduler's queue, submission to admission into a
slot, over the requests admitted in the window: the program's
``scheduler.queued`` spans. Host clock, ms."""
from harness import program


def read(run):
    return program.median_ms(run, "scheduler.queued")
