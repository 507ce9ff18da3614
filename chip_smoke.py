"""Chip smoke: the FNO train -> serve path on a TPU, in this one process.

One chip (no arguments):
  1. ``train.py --mode fno --config fno-sleipner`` for 5 steps from seeded
     synthetic data that carries the static geomodel channel, saving a
     checkpoint. Published widths (width 40, decoder 128, 4 blocks, m_x 24,
     m_t 10), as one chip's share of the 8x4 pencil deployment with the
     grid cut to fit: ``ONE_CHIP_OVERRIDES`` in configs/fno_sleipner.py
     states the share and each cut.
  2. ``serve_pde.py`` from that checkpoint: ``FNORunner`` + ``Scheduler``,
     the ``--ensemble`` geomodel cache at ``--cache-level deep``, the fused
     Pallas kernel, 8 scenarios x 2 rollout steps, and ``--verify`` against
     the serial float32 reference.

``--four-chips`` runs only the 2x2 pencil: ``train.py --model-shards 2 2``
then ``serve_pde.py --model-shards 2 2 --verify`` on four chips
(``FOUR_CHIP_OVERRIDES``), checked against the serial float32 reference
on gathered parameters.

Every line before the last is smoke output, not a benchmark number. The
last line is ``{"ok": true, "device": {...}}``. Any failed phase raises and
the script exits non-zero without it; so does a run that finds no TPU.

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

CONFIG = "fno-sleipner"
TRAIN_STEPS = 5
SCENARIOS = 8
ROLLOUT_STEPS = 2
# one geomodel's entry is ~0.36 GB at the one-chip grid, mostly its prelift
CACHE_BYTES = 1 << 30


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip smoke FAILED: {what}")


def run(config: str, overrides: dict, model_shards, n_devices: int) -> None:
    from repro.configs import fno_with_overrides, get_fno
    from repro.launch import serve_pde, train

    ckpt = os.path.join(ROOT, "artifacts", "chip_smoke", "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)  # a stale checkpoint would restore
    published, _ = get_fno(config)
    cfg = fno_with_overrides(config, overrides)
    say(f"{config}: width {cfg.width}, decoder {cfg.decoder_dim}, "
        f"{cfg.n_blocks} blocks, modes {cfg.modes} (published "
        f"{published.modes}), grid {cfg.grid} (published {published.grid}), "
        f"in_channels {cfg.in_channels}; model shards "
        f"{'x'.join(map(str, model_shards))} on {n_devices} device(s)")
    overrides = [
        f"{k}={','.join(map(str, v)) if isinstance(v, tuple) else v}"
        for k, v in overrides.items()
    ]
    shards = [str(s) for s in model_shards]

    t0 = time.perf_counter()
    result = train.main(
        ["--mode", "fno", "--config", config, "--geomodel",
         "--steps", str(TRAIN_STEPS), "--batch", "1", "--n-data", "4",
         "--ckpt-dir", ckpt, "--devices", str(n_devices),
         "--model-shards", *shards, "--compile-report"]
        + [a for o in overrides for a in ("--override", o)]
    )
    losses = [m["loss"] for _, m in result.metrics_log]
    say(f"train: {result.final_step} steps, failures {result.failures}, "
        f"restores {result.restores}, loss {losses[0]:.6e} -> "
        f"{losses[-1]:.6e}, compile {result.info['compile_s']:.1f}s, "
        f"phase {time.perf_counter() - t0:.1f}s (smoke, not a benchmark)")
    check(result.final_step == TRAIN_STEPS and len(losses) == TRAIN_STEPS,
          f"trained {result.final_step}/{TRAIN_STEPS} steps")
    check(result.failures == 0 and result.restores == 0,
          f"{result.failures} failure(s), {result.restores} restore(s)")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")

    t0 = time.perf_counter()
    summary = serve_pde.main(
        ["--ckpt-dir", ckpt, "--scenarios", str(SCENARIOS),
         "--max-batch", "2", "--rollout-steps", str(ROLLOUT_STEPS),
         "--ensemble", "--static-channels", "1", "--cache-level", "deep",
         "--cache-bytes", str(CACHE_BYTES), "--use-pallas", "--verify",
         "--model-shards", *shards]
    )
    runner = summary["runners"][0]
    cache = runner.cache.stats
    say(f"serve: {summary['served']} scenarios x {ROLLOUT_STEPS} rollout "
        f"steps, verify max abs diff {summary['verify_max_abs']:.3e}, "
        f"geomodel cache {cache['hits']} hits / {cache['misses']} misses, "
        f"compile {summary['compile_s']:.1f}s, phase "
        f"{time.perf_counter() - t0:.1f}s (smoke, not a benchmark)")
    check(summary["served"] == SCENARIOS,
          f"served {summary['served']}/{SCENARIOS}")
    check(cache["hits"] > 0 and cache["evictions"] == 0,
          f"the geomodel cache never hit: {cache}")
    mesh_ids = sorted(d.id for d in runner.mesh.devices.flat)
    say(f"serving mesh {dict(runner.mesh.shape)} on device ids {mesh_ids}")
    check(len(mesh_ids) == n_devices,
          f"serving mesh spans {len(mesh_ids)} of {n_devices} devices")
    compiled = runner.compiled_step(runner.buckets[-1])
    mem = compiled.memory_analysis()
    say(f"serving step bucket {runner.buckets[-1]}: compiled bytes argument "
        f"{mem.argument_size_in_bytes / 1e9:.2f} GB temp "
        f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    check("tpu_custom_call" in compiled.as_text(),
          "use_pallas is on but the compiled serving step has no "
          "tpu_custom_call (the Pallas kernel is not in it)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 pencil train -> serve on 4 chips")
    args = ap.parse_args(argv)

    import jax

    from repro.common.compile_cache import enable_compile_cache
    from repro.configs import fno_sleipner

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    n = 4 if args.four_chips else 1
    check(len(devices) >= n, f"{n} chips needed, JAX found {len(devices)}")
    say("smoke output below; no line is a benchmark number")
    say(f"device {dev.device_kind!r} x {len(devices)}, jax {jax.__version__}, "
        f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '<unset>')!r}, "
        f"compile cache {enable_compile_cache()}")
    if args.four_chips:
        run(CONFIG, fno_sleipner.FOUR_CHIP_OVERRIDES, (2, 2), n)
    else:
        run(CONFIG, fno_sleipner.ONE_CHIP_OVERRIDES, (1,), n)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say(f"device 0 peak bytes in use {stats['peak_bytes_in_use'] / 1e9:.2f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
