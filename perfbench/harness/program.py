"""What the program records about itself, as the per-layer metrics read it.

The program keeps its own host spans (``repro.common.tracing``: name,
start and end on ``time.perf_counter``, attributes) and names the stages of
its model with ``jax.named_scope``, which the trace shows in each device
op's JAX op path (``tf_op``). A program that records neither gives the
readers nothing, and they return None.

``records`` returns the program's spans that lie inside the benchmark's
``window`` span (``run.spans``, the same clock). ``to_device`` maps a time
of that clock onto the trace's (ns), by the offset between the window's
start in the trace and in ``run.spans``. ``under`` tests whether an op's
JAX op path holds a scope as one of its segments, bare or wrapped by
transforms (``blocks/``, ``jvp(blocks)/``, ``transpose(jvp(blocks))/``).
"""
from __future__ import annotations

import re
import statistics


def _window_s(run):
    ws = [(a, b) for n, a, b in run.spans.records if n == "window"]
    return ws[-1] if ws else None


def records(run, name: str) -> list:
    """The program's spans named ``name`` that start and end inside the
    window; [] where the program records none."""
    try:
        from repro.common import tracing
    except ImportError:
        return []
    w = _window_s(run)
    if w is None:
        return []
    return [r for r in tracing.records()
            if r.name == name and w[0] <= r.start and r.end <= w[1]]


def median_ms(run, name: str):
    """Median length of the window's spans named ``name``, ms; None where
    there are none."""
    spans = records(run, name)
    return 1e3 * statistics.median(r.end - r.start for r in spans) if spans else None


def to_device(run, t: float) -> float:
    """A ``perf_counter`` time in seconds, on the trace's clock in ns."""
    return t * 1e9 + run.trace.window[0] - _window_s(run)[0] * 1e9


def under(tf_op: str, *scopes: str) -> bool:
    """Whether the op path ``tf_op`` lies under any of ``scopes``."""
    return any(re.search(r"(?:^|[/;])(?:[\w.-]*\()*" + re.escape(s) + r"\)*(?:[/:;]|$)",
                         tf_op) for s in scopes)


def mean_ms(run, where, per: str):
    """Device time of the ops ``where`` accepts, mean over the chips, over
    ``run.counts[per]``, ms; None where no op of the window is accepted."""
    t = run.trace
    if not any(t.ops(d, where) for d in range(len(t.devices))):
        return None
    return 1e3 * t.mean_op_seconds(where) / run.counts[per]
