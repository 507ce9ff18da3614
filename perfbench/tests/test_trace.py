"""The reduction from trace to metrics: on a synthetic trace with known
answers, and on traces recorded on a TPU v5e chip (reduced form, kept in
``tests/data``: one traced window of each one-chip cell)."""
import os

import pytest

from harness import cell as cell_lib
from harness.peaks import peaks
from harness.spans import Spans
from harness.trace import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic() -> Trace:
    ops = [Op("a", 0, 10, "loop fusion"), Op("w", 5, 40, "while"),
           Op("b", 5, 20, "convolution fusion", "jit(f)/jit(fft):"),
           Op("c", 30, 40, "custom-call"), Op("d", 90, 130, "loop fusion")]
    spans = [("window", 0, 100), ("tick", 0, 50), ("submit", 60, 80)]
    return Trace([ops], spans)


def test_busy_union_and_gaps():
    t = synthetic()
    assert t.window_s == pytest.approx(100e-9)
    # union of [0,40] (the while spans its body) and [90,100] (clipped)
    assert t.busy_s(0) == pytest.approx(50e-9)
    assert t.idle_gaps(0) == [(40, 90)]
    assert t.breakdown()["idle_gaps"] == [["submit", pytest.approx(50e-9)]]


def test_op_time_leaves_out_containers_and_clips():
    t = synthetic()
    assert [o.name for o in t.ops(0)] == ["a", "b", "c", "d"]
    assert t.op_seconds(0, lambda o: o.name == "d") == pytest.approx(10e-9)
    assert t.op_seconds(0, lambda o: "fft" in o.tf_op) == pytest.approx(15e-9)
    names = [n for n, _ in t.breakdown()["device_ops"]]
    assert "w [while]" not in names
    assert names[0] == "jit(f)/jit(fft): [convolution fusion]"


def test_span_at_is_innermost():
    t = synthetic()
    assert t.span_at(10) == "tick"
    assert t.span_at(70) == "submit"
    assert t.span_at(85) == "window"
    assert t.span_at(150) == "outside"


def recorded(workload: str):
    t = Trace.read(os.path.join(DATA, f"{workload}.json.gz"))
    cell = cell_lib.load(workload)
    spans = Spans()
    spans.records = [(n, a * 1e-9, b * 1e-9) for n, a, b in t.spans]
    ticks = len(spans.durations("tick"))
    counts = {"bucket": cell.traffic.get("slots"), "batch": cell.traffic.get("batch"),
              "steps": len(spans.durations("step")), "rollout_steps": 2 * ticks,
              "window_s": t.window_s}
    import run

    r = run.Run(cell, cell.model(), peaks("TPU v5 lite"), counts, t, spans)
    return t, cell, {m["name"]: cell.reader(m["name"])(r) for m in cell.per_layer}


@pytest.mark.parametrize("workload", ["sleipner-serve-ensemble", "sleipner-serve-realizations"])
def test_recorded_serve(workload):
    t, cell, got = recorded(workload)
    ticks = sum(1 for n, *_ in t.spans if n == "tick")
    kernel = [o for o in t.ops(0) if o.category == "custom-call"
              and o.name.startswith("spectral_fused")]
    # one fused mix per block per tick
    assert len(kernel) == cell.config["n_blocks"] * ticks
    assert 0 < got["spectral_mix_roofline.serve"] < 100
    assert 0 < got["device_idle_share.serve"] < 100
    assert 0 < got["serve_mfu"] < 100
    assert got["tick_ms.serve"] > 0


def test_recorded_train():
    t, cell, got = recorded("sleipner-train")
    assert 0 < got["fft_ms_per_step.train"] < 1e3 * t.window_s
    assert 0 <= got["device_idle_share.train"] < 100
    assert 0 < got["train_mfu"] < 100
    assert t.busy_s(0) <= t.window_s
