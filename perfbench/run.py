"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout. The cell, its configuration, traffic mix,
limits and per-layer metrics are found by name (``harness/cell.py``).
Set-up builds the program's objects from the seed and warms every shape
the window uses; the window then runs for ``--seconds``; then the run's
outputs are compared with the plain reference. With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

The last lines on standard error are the numbers compared, each beside its
limit; the last line on standard output is the result as one JSON object.
A run that finds no TPU, or fewer chips than the cell asks for, exits 1
and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

from harness import cell as cell_lib  # noqa: E402
from harness.spans import Spans  # noqa: E402

# JAX's persistent compilation cache: one fixed directory inside the
# checkout (the path is part of an entry's key), so only a cell's first run
# in a checkout compiles.
COMPILE_CACHE = os.path.join(cell_lib.ROOT, ".jax_cache")


class Run:
    """What a per-layer metric reader gets: the cell, what the driver
    counted in the window, and the reduced trace."""

    def __init__(self, cell, model, peaks, counts, trace, spans):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.model = model
        self.peaks = peaks
        self.counts = counts
        self.trace = trace
        self.spans = spans


def fail(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def devices_for(chips: int, require_tpu: bool):
    """The first ``chips`` devices; with ``require_tpu``, after pointing
    JAX's persistent compilation cache at ``COMPILE_CACHE``, and failing
    unless they are TPU chips."""
    import jax

    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    """The fullest chip's peak. On the TPU, ``peak_bytes_in_use`` counts
    the buffers the runtime hands out (weights, state, inputs, outputs);
    the temporaries of a loaded program are reserved apart, and counted in
    ``peak_bytes_reserved``."""
    stats = [d.memory_stats() or {} for d in devs]
    return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)


def verdict(checks: dict) -> bool:
    """``correct``: some number is compared, and each is within its limit."""
    return bool(checks) and all(v["value"] <= v["limit"] for v in checks.values())


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, dump_trace: str | None = None) -> dict:
    import jax

    from harness import peaks as peaks_lib
    from harness import trace as trace_lib

    devs = devices_for(cell.chips, require_tpu)
    spans = Spans()
    model = cell.model()
    driver = cell.driver().Driver(cell, model, devs, seed, spans)
    driver.setup()
    setup_s = time.perf_counter() - PROCESS_START

    reduced = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        spans.annotate = True
    try:
        with spans("window"):
            counts = driver.window(seconds)
    finally:
        if trace:
            spans.annotate = False
            jax.profiler.stop_trace()
    if trace:
        try:
            if dump_trace:
                os.makedirs(dump_trace, exist_ok=True)
                for p in glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*")):
                    shutil.copy(p, dump_trace)
            reduced = trace_lib.load(tmp, len(devs))
            if dump_trace:
                reduced.save(os.path.join(dump_trace, f"{cell.name}.trace.json.gz"))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    memory_peak = peak_bytes(devs)
    end_to_end = dict(driver.end_to_end(), setup_s=setup_s)
    driver.release()
    gc.collect()
    checks = driver.check()

    correct = verdict(checks)
    metrics = {}
    if trace:
        run = Run(cell, model, peaks_lib.peaks(devs[0].device_kind), counts,
                  reduced, spans)
        for m in cell.per_layer:
            value = cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also keep the raw and the reduced trace in this directory")
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a non-negative integer")
    cell = cell_lib.load(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      dump_trace=args.dump_trace)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
