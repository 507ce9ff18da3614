"""Median time of the program's ``fno_runner.stage`` span over the
window's ticks: building the bucket's host input arrays (geomodel cache
lookups, copies into the batch). Host clock, ms."""
from harness import program


def read(run):
    return program.median_ms(run, "fno_runner.stage")
