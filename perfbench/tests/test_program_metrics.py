"""The readers of the program's own spans and scopes (``harness/program.py``
and the seven metrics that use it): known answers on a synthetic trace whose
device clock is offset from the host's, with scope names wrapped by
transforms; nothing (None) on the recorded traces, which predate the
program's spans and scopes; and the bytes a tiny serving window uploads,
read back from what the program recorded."""
import os

import jax
import numpy as np
import pytest

import run
from harness import cell as cell_lib
from harness import program
from harness.spans import Spans
from harness.trace import Op, Trace
from repro.common import tracing
from tests import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("stage_ms.serve", "transfer_idle_ms.serve", "feedback_ms.serve",
       "h2d_mb_per_tick.serve", "queue_wait_ms.serve", "fft_ms_per_tick.serve",
       "scan_ms_per_step.train")
MS = 1e6            # ns
T0 = 100.0          # the window's start on the host clock, s
D0 = 5_000_000.0    # ... and on the device clock, ns


@pytest.fixture(autouse=True)
def fresh_recorder():
    tracing.clear()
    yield
    tracing.clear()


def host(ms):
    return T0 + ms * 1e-3


def dev(ms):
    return D0 + ms * MS


def synthetic_run():
    """Two ticks in a 10 ms window; device 1 runs nothing."""
    spans = Spans()
    spans.records = [("window", host(0), host(10))]
    for name, a, b, attrs in [
        ("fno_runner.stage", 1, 2, {}), ("fno_runner.forward", 2, 4, {"bytes": 3_000_000}),
        ("fno_runner.feedback", 4, 4.5, {}),
        ("fno_runner.stage", 5, 6.5, {}), ("fno_runner.forward", 6.5, 8.5, {"bytes": 5_000_000}),
        ("fno_runner.feedback", 8.5, 9.5, {}),
        ("scheduler.queued", 0, 1, {"rid": 0}), ("scheduler.queued", 0.2, 5, {"rid": 1}),
        ("scheduler.queued", -1000, 1, {"rid": 9}),    # submitted before the window
        ("fno_runner.stage", -500, -400, {}),          # before the window
    ]:
        tracing.record(name, host(a), host(b), **attrs)
    serve = "jit(forward)/blocks/while/body/closed_call/checkpoint"
    train = "jit(train_step)"
    ops = [
        Op("f", dev(2.5), dev(3.5), "convolution fusion", f"{serve}/fft_fwd/jit(fft):"),
        Op("k", dev(6.5), dev(8.0), "custom-call",
           f"{serve}/mix/jit(spectral_fused_pallas)/spectral_fused/pallas_call"),
        Op("i", dev(7.0), dev(7.5), "loop fusion", "jit(forward)/blocks/fft_inv/jit(fft):"),
        Op("s", dev(0.0), dev(2.0), "loop fusion", f"{train}/transpose(jvp(blocks))/while:"),
        Op("d", dev(9.0), dev(10.0), "data formatting",
           f"{train}/jvp(blocks)/while/body/closed_call/dynamic_slice"),
        Op("r", dev(4.0), dev(4.2), "loop fusion", f"{train}/transpose(jvp(blocks))/while/body/"
           "closed_call/checkpoint/rematted_computation/fft_fwd/jit(fft):"),
        Op("e", dev(4.2), dev(4.4), "convolution fusion", f"{train}/jvp(encoder)/dot_general"),
        Op("l", dev(4.4), dev(4.6), "loop fusion", f"{train}/jvp()/while:"),
        Op("w", dev(0.0), dev(2.0), "while", f"{train}/jvp(blocks)/while"),
    ]
    trace = Trace([sorted(ops, key=lambda o: o.start), []],
                  [("window", dev(0), dev(10))])
    counts = {"ticks": 2, "steps": 2}
    return run.Run(cell_lib.load("sleipner-serve-ensemble"), None, None, counts, trace, spans)


def read(name, r):
    return cell_lib.load("sleipner-train" if name.endswith(".train") else
                         "sleipner-serve-ensemble").reader(name)(r)


EXPECTED = {
    "stage_ms.serve": 1.25,            # median of 1 and 1.5
    "feedback_ms.serve": 0.75,         # median of 0.5 and 1
    "h2d_mb_per_tick.serve": 4.0,      # median of 3 and 5 MB
    "queue_wait_ms.serve": 2.9,        # median of 1 and 4.8; rid 9 was queued before
    # forward 1: device 0 busy 1 of 2 ms, device 1 idle: (1 + 2) / 2;
    # forward 2: device 0 busy 6.5-8.0 of 2 ms: (0.5 + 2) / 2; median
    "transfer_idle_ms.serve": (1.5 + 1.25) / 2,
    # both transforms and the remat'd one of the train ops (one trace holds
    # both kinds), mean over 2 chips, over 2 ticks
    "fft_ms_per_tick.serve": (1.0 + 0.5 + 0.2) / 2 / 2,
    # the scan's loop fusion and dynamic slice; not the staged FFT, the
    # encoder, an unscoped while, or the container
    "scan_ms_per_step.train": (2.0 + 1.0) / 2 / 2,
}


@pytest.mark.parametrize("name", NEW)
def test_synthetic(name):
    assert read(name, synthetic_run()) == pytest.approx(EXPECTED[name])


def test_under_scope_segments():
    assert program.under("jit(f)/transpose(jvp(blocks))/while:", "blocks")
    assert program.under("jit(f)/jvp(blocks)/while/body/mix/pallas_call", "mix")
    assert program.under("blocks/while", "blocks")
    assert program.under("jit(f)/a;jit(f)/bypass/tanh", "bypass")
    assert not program.under("jit(f)/blocks_extra/while", "blocks")
    assert not program.under("jit(f)/jvp()/while:", "blocks")
    assert not program.under("jit(f)/while/body/jit(fft):", "fft_fwd", "fft_inv")


def recorded_run(workload):
    t = Trace.read(os.path.join(DATA, f"{workload}.json.gz"))
    spans = Spans()
    spans.records = [(n, a * 1e-9, b * 1e-9) for n, a, b in t.spans]
    counts = {"ticks": sum(1 for n, *_ in t.spans if n == "tick"),
              "steps": sum(1 for n, *_ in t.spans if n == "step")}
    return run.Run(cell_lib.load(workload), None, None, counts, t, spans)


@pytest.mark.parametrize("workload", ["sleipner-serve-ensemble", "sleipner-train",
                                      "sleipner-serve-realizations"])
def test_recorded_traces_read_nothing(workload):
    r = recorded_run(workload)
    names = [m["name"] for m in r.cell.per_layer if m["name"] in NEW]
    assert names
    for name in names:
        assert r.cell.reader(name)(r) is None, name


def test_serving_window_uploads_what_the_program_records():
    """A tiny ensemble window on the CPU: the host readers find one stage,
    forward and feedback a tick, and the bytes of the deep split's bucket."""
    cell = tiny.cell("sleipner-serve-ensemble")
    model, spans = cell.model(), Spans()
    driver = cell.driver().Driver(cell, model, jax.devices()[:1], 2**33 + 5, spans)
    driver.setup()
    with spans("window"):
        counts = driver.window(0.5)
    r = run.Run(cell, model, None, counts, None, spans)
    cfg, tr = cell.config, cell.traffic
    n = int(np.prod(cfg["grid"]))
    n_dyn = cfg["in_channels"] - tr["n_static"]
    kept = 8 * int(np.prod(cfg["modes"][:3])) * cfg["modes"][3]
    per_row = 4 * cfg["width"] * n + 4 * n_dyn * n + 8 * cfg["width"] * kept
    assert read("h2d_mb_per_tick.serve", r) == pytest.approx(tr["slots"] * per_row / 1e6)
    assert len(program.records(r, "fno_runner.stage")) == counts["ticks"]
    for name in ("stage_ms.serve", "feedback_ms.serve", "queue_wait_ms.serve"):
        assert read(name, r) > 0, name
