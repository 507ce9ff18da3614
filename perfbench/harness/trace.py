"""Reduction of a JAX profiler trace to what the per-layer metrics read.

``load`` reads the trace the profiler writes beside its ``.xplane.pb``
(``*.trace.json.gz``, Chrome trace format, microseconds): for every device
(``/device:TPU:<n>``) the events of its ``XLA Ops`` line, with the HLO
category and the JAX op path (``tf_op``, e.g. ``jit(train_step)/jvp()/
while/body/closed_call/jit(fft):``) of each, and from the host the
benchmark's own spans (``bench.<name>``), all in nanoseconds on one clock.
``Trace`` answers the questions the metric readers ask: device busy time
inside the traced window (the union of op intervals), the time of chosen
ops, and the idle gaps with the host span that was open in each.

Container ops (a ``while`` loop and the like) span their bodies' ops: they
count toward busy time but not toward the time of any op.

A ``Trace`` also round-trips through a small JSON form, which is how the
recorded trace used by the tests is kept.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

from harness.spans import PREFIX

OPS_LINE = "XLA Ops"
DEVICE = re.compile(r"^/device:TPU:(\d+)$")
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str
    start: float   # ns
    end: float     # ns
    category: str = ""
    tf_op: str = ""

    @property
    def label(self) -> str:
        """What the op computes, without instance numbers: its JAX op path
        and HLO category."""
        return re.sub(r"\.\d+", "", f"{self.tf_op or self.name} [{self.category}]")


@dataclasses.dataclass
class Trace:
    devices: list   # one sorted list of Op per device plane, by device id
    spans: list     # (name, start_ns, end_ns), host spans of the benchmark

    # -- the window ----------------------------------------------------
    @property
    def window(self) -> tuple:
        ws = [(a, b) for n, a, b in self.spans if n == "window"]
        if not ws:
            raise ValueError("the trace holds no bench.window span")
        return ws[-1]

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) * 1e-9

    def ops(self, dev: int, where=None, containers: bool = False) -> list:
        """Ops of device ``dev`` that overlap the window, clipped to it;
        with ``where``, only those it accepts."""
        w0, w1 = self.window
        out = []
        for op in self.devices[dev]:
            if op.end <= w0 or op.start >= w1:
                continue
            if not containers and op.category in CONTAINERS:
                continue
            if where is not None and not where(op):
                continue
            out.append(Op(op.name, max(op.start, w0), min(op.end, w1), op.category, op.tf_op))
        return out

    # -- busy, idle ----------------------------------------------------
    def busy_intervals(self, dev: int) -> list:
        merged = []
        for op in sorted(self.ops(dev, containers=True), key=lambda o: o.start):
            if merged and op.start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], op.end)
            else:
                merged.append([op.start, op.end])
        return merged

    def busy_s(self, dev: int) -> float:
        return sum(b - a for a, b in self.busy_intervals(dev)) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in range(len(self.devices))) / len(self.devices)

    def idle_gaps(self, dev: int) -> list:
        """(start_ns, end_ns) of every stretch of the window in which no
        op ran on ``dev``."""
        w0, w1 = self.window
        gaps, t = [], w0
        for a, b in self.busy_intervals(dev):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if w1 > t:
            gaps.append((t, w1))
        return gaps

    def span_at(self, t: float) -> str:
        """The innermost benchmark span open at ``t`` (the window itself
        when no other is)."""
        best = None
        for n, a, b in self.spans:
            if a <= t <= b and (best is None or b - a < best[2] - best[1]):
                best = (n, a, b)
        return best[0] if best else "outside"

    def op_seconds(self, dev: int, where) -> float:
        return sum(o.end - o.start for o in self.ops(dev, where)) * 1e-9

    def mean_op_seconds(self, where) -> float:
        n = len(self.devices)
        return sum(self.op_seconds(d, where) for d in range(n)) / n

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took the most device time (mean over devices) and
        the longest idle gaps of device 0, each named by its host span."""
        n = len(self.devices)
        by_name = {}
        for d in range(n):
            for o in self.ops(d):
                by_name[o.label] = by_name.get(o.label, 0.0) + (o.end - o.start) * 1e-9 / n
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[self.span_at((a + b) / 2), (b - a) * 1e-9] for a, b in gaps],
        }

    # -- storage -------------------------------------------------------
    def to_json(self) -> dict:
        return {"devices": [[[o.name, o.start, o.end, o.category, o.tf_op] for o in ops]
                            for ops in self.devices],
                "spans": [list(s) for s in self.spans]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls([[Op(*o) for o in ops] for ops in d["devices"]],
                   [tuple(s) for s in d["spans"]])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def read(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def load(log_dir: str, n_devices: int) -> Trace:
    """Reduce the profiler output under ``log_dir`` for the first
    ``n_devices`` devices."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.trace.json.gz"))
    if len(paths) != 1:
        raise ValueError(f"expected one .trace.json.gz under {log_dir}, found {paths}")
    return load_file(paths[0], n_devices)


def load_file(path: str, n_devices: int) -> Trace:
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices, spans = {}, []
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        proc = procs.get(e["pid"], "")
        t0, t1 = e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3
        m = DEVICE.match(proc)
        if m and threads.get((e["pid"], e.get("tid"))) == OPS_LINE:
            args = e.get("args", {})
            devices.setdefault(int(m.group(1)), []).append(
                Op(e["name"], t0, t1, args.get("hlo_category", ""), args.get("tf_op", "")))
        elif proc.startswith("/host:") and e["name"].startswith(PREFIX):
            spans.append((e["name"][len(PREFIX):], t0, t1))
    ids = sorted(devices)[:n_devices]
    if len(ids) < n_devices:
        raise ValueError(f"the trace holds ops of {len(ids)} devices, "
                         f"{n_devices} were used")
    return Trace([sorted(devices[i], key=lambda o: o.start) for i in ids],
                 sorted(spans, key=lambda s: s[1]))
