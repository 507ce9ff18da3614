"""Pre-jax device-count plumbing shared by the launch CLIs.

``--devices N`` on CPU means "simulate N host devices", which XLA only
honors if ``--xla_force_host_platform_device_count`` is set BEFORE the
first jax import. Each CLI therefore sniffs argv and sets the flag at the
very top of its module, before importing anything that imports jax — which
is why this module must never import jax (directly or transitively).
"""
from __future__ import annotations

import os


def sniff_devices(argv):
    """Pre-argparse --devices value, handling BOTH ``--devices N`` and
    ``--devices=N`` (the latter used to be silently ignored, running on one
    device). Must be evaluated before any jax import."""
    for i, tok in enumerate(argv):
        if tok == "--devices" and i + 1 < len(argv):
            return argv[i + 1]
        if tok.startswith("--devices="):
            return tok.split("=", 1)[1]
    return None


def apply_device_flag(argv) -> None:
    """Set the XLA host-device-count flag if argv carries --devices. It
    only sizes the CPU backend; on a TPU the device count is the chip's."""
    n = sniff_devices(argv)
    if n is not None:
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
