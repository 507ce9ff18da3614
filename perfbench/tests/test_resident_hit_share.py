"""The reader of ``resident_hit_share.serve``: a known share from synthetic
stage spans, nothing (None) where no span carries the attributes, and a
tiny ensemble window on the CPU, whose ticks read every geomodel row from
the program's device table and upload only the dynamic channel."""
import jax
import numpy as np
import pytest

import run
from harness import cell as cell_lib
from harness.spans import Spans
from repro.common import tracing
from tests import tiny

NAME = "resident_hit_share.serve"


@pytest.fixture(autouse=True)
def fresh_recorder():
    tracing.clear()
    yield
    tracing.clear()


def read(name, r):
    return r.cell.reader(name)(r)


def window_run(stages):
    """A 10 s window on the host clock holding one stage span per
    attribute dict, and one before the window."""
    spans = Spans()
    spans.records = [("window", 100.0, 110.0)]
    tracing.record("fno_runner.stage", 90.0, 91.0, resident_hits=0, resident_fills=5)
    for k, attrs in enumerate(stages):
        tracing.record("fno_runner.stage", 101.0 + k, 101.5 + k, **attrs)
    return run.Run(cell_lib.load("sleipner-serve-ensemble"), None, None, {}, None, spans)


def test_share_of_rows_served_from_the_table():
    r = window_run([{"resident_hits": 0, "resident_fills": 2},
                    {"resident_hits": 2, "resident_fills": 0},
                    {"resident_hits": 1, "resident_fills": 1}])
    assert read(NAME, r) == pytest.approx(3 / 6)


@pytest.mark.parametrize("stages", [[], [{}, {}]], ids=["no_spans", "no_attrs"])
def test_nothing_to_read(stages):
    assert read(NAME, window_run(stages)) is None


def test_ensemble_window_reads_every_row_from_the_table():
    """Set-up fills the table; every window tick then hits it, and the
    forward's upload is the bucket's dynamic channel alone."""
    cell = tiny.cell("sleipner-serve-ensemble")
    model, spans = cell.model(), Spans()
    driver = cell.driver().Driver(cell, model, jax.devices()[:1], 2**33 + 7, spans)
    driver.setup()
    with spans("window"):
        counts = driver.window(0.5)
    r = run.Run(cell, model, None, counts, None, spans)
    cfg, tr = cell.config, cell.traffic
    n_dyn = cfg["in_channels"] - tr["n_static"]
    xd = tr["slots"] * 4 * n_dyn * int(np.prod(cfg["grid"]))
    assert read(NAME, r) == 1.0
    assert read("h2d_mb_per_tick.serve", r) == pytest.approx(xd / 1e6)
    assert driver.runner.resident_fills == 1
