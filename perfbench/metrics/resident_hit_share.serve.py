"""Share of the static geomodel rows the window's ticks read from the
program's device table: over the ``fno_runner.stage`` spans that carry the
attributes, the ``resident_hits`` summed over the hits and the
``resident_fills`` (rows uploaded into the table). None where no span
carries them: a program without the table, or a runner without static
channels or a cache."""
from harness import program


def read(run):
    spans = [r.attrs for r in program.records(run, "fno_runner.stage")
             if "resident_hits" in r.attrs]
    rows = sum(a["resident_hits"] + a["resident_fills"] for a in spans)
    return sum(a["resident_hits"] for a in spans) / rows if rows else None
