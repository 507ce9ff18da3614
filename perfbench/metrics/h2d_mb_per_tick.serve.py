"""Median over the window's ticks of the host input bytes each tick's
forward uploads: the ``bytes`` attribute of the program's
``fno_runner.forward`` span (the summed size of the host arrays passed to
the jitted forward). MB."""
import statistics

from harness import program


def read(run):
    spans = program.records(run, "fno_runner.forward")
    return statistics.median(r.attrs["bytes"] for r in spans) / 1e6 if spans else None
