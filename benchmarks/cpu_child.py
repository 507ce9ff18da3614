"""Run an HLO-analysis script in a child process pinned to the CPU.

The analyses lower programs for N simulated host devices. Their parent
may hold an accelerator already (a chip belongs to one process), so each
child is told ``JAX_PLATFORMS=cpu`` in its own environment, next to the
host device count.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


def cpu_env(n_devices: int = 1) -> dict:
    """The parent's environment with the child pinned to N CPU devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


def run_cpu_script(script: str, n_devices: int = 1, timeout: int = 900) -> dict:
    """Run ``script`` (``python -c``) on N CPU devices and return the JSON
    object it prints after ``RESULT``; raise with its output otherwise."""
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout, env=cpu_env(n_devices),
    )
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            return json.loads(line[len("RESULT"):])
    raise RuntimeError(proc.stdout[-1500:] + proc.stderr[-2500:])
