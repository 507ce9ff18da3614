"""Median time of the program's ``fno_runner.feedback`` span over the
window's ticks: de-normalizing the outputs, the rollout feedback and its
re-encoding. Host clock, ms."""
from harness import program


def read(run):
    return program.median_ms(run, "fno_runner.feedback")
