"""Serve the trained CO2 surrogate: UQ-ensemble inference through the
family-generic scheduler.

The paper's payoff workload: thousands of sequential simulations (well-
placement optimization, uncertainty quantification) become tractable when
the surrogate replaces the numerical simulator. This driver draws N
permeability/well-placement scenarios from the ``two_phase`` generator,
serves them through the shared slot scheduler with model-parallel FNO
inference (``FNORunner.from_checkpoint``), and reports scenarios/s plus
per-request latency.

    PYTHONPATH=src python -m repro.launch.datagen --pde two_phase --n 8 \
        --grid 16 8 8 --nt 4 --out /tmp/co2_ds
    PYTHONPATH=src python src/repro/launch/train.py --mode fno \
        --x-store /tmp/co2_ds/x --y-store /tmp/co2_ds/y --ckpt-dir /tmp/ck
    PYTHONPATH=src python src/repro/launch/serve_pde.py --ckpt-dir /tmp/ck \
        --scenarios 8 --verify --bench-sequential

``--verify`` replays every served scenario through the serial
``fno_forward`` oracle (same normalization chain) and fails loudly on
mismatch; ``--bench-sequential`` also serves the ensemble one-at-a-time
through a single-slot scheduler over the same warm runner, reporting the
continuous-batching speedup; ``--reference`` times the numerical simulator
on one scenario for the paper's surrogate-vs-simulator speedup.
"""
import argparse
import sys
import time

import numpy as np

from repro.launch.devices import apply_device_flag


def build_scenarios(cfg, n: int, wells: int, seed: int, steps: int,
                    n_static: int = 0, dup: int = 1):
    """N well-placement scenarios in the model's input layout.

    ``n_static > 0`` builds the UQ-ensemble workload: the first channels
    are the SHARED log-permeability geomodel (byte-identical across every
    scenario — ``datagen --geomodel``'s construction, so a checkpoint
    trained on such a store serves in-distribution), only the well channel
    varies. ``dup`` submits each scenario that many times (duplicates get
    fresh rids; the scheduler dedups them in flight).
    """
    from repro.data.pde.two_phase import TwoPhaseConfig, random_well_mask
    from repro.launch.datagen import geomodel_channel
    from repro.serve import ScenarioRequest

    nx, ny, nz, nt = cfg.grid
    sim_cfg = TwoPhaseConfig(grid=(nx, ny, nz), nt_frames=nt)
    geo = None
    if n_static:
        one = geomodel_channel((nx, ny, nz), nt)
        geo = np.concatenate([one] * n_static, axis=0)[:n_static]
    requests, rid = [], 0
    n_dyn = cfg.in_channels - n_static
    for i in range(n):
        mask = random_well_mask(sim_cfg, wells, seed + i)
        x = np.repeat(
            mask[None, :, :, :, None], nt, axis=-1
        ).astype(np.float32)
        if n_dyn > 1:
            x = np.concatenate([x] * n_dyn, axis=0)[:n_dyn]
        if geo is not None:
            x = np.concatenate([geo, x], axis=0)
        for _ in range(max(1, dup)):
            requests.append(ScenarioRequest(rid=rid, x=x.copy(), steps=steps))
            rid += 1
    return requests, sim_cfg


def oracle_rollout(runner, x_raw: np.ndarray, steps: int):
    """Per-request reference: serial fno_forward (batch 1) through the same
    normalize -> forward -> de-normalize -> feedback chain.

    A plain float32 reference: every contraction at full float32 precision
    (``jax.default_matmul_precision("highest")``; a TPU's default is one
    bf16 pass), the unfused forward, and the runner's params gathered to
    the host and placed whole on one device — a single-device program, not
    a re-partition of the serving mesh's sharded tree.
    """
    import dataclasses

    import jax

    from repro.core import fno_forward
    from repro.core.fno import params_without_planes

    cached = getattr(runner, "_oracle_cache", None)
    if cached is None:
        # one gather + one jit for ALL oracle calls against this runner (a
        # fresh lambda per call would recompile the serial FNO once per
        # scenario). The oracle is the UNFUSED serial forward on complex
        # params: when the runner serves the fused Pallas path (plane-cached
        # params), --verify is a true fused-vs-unfused equivalence gate,
        # not a self-comparison.
        oracle_cfg = dataclasses.replace(runner.cfg, use_pallas=False)
        params = params_without_planes(jax.device_get(runner.params))
        cached = runner._oracle_cache = (
            jax.device_put(params, jax.devices()[0]),
            jax.jit(lambda p, x: fno_forward(p, x, oracle_cfg)),
        )
    params, fwd = cached
    n_static = getattr(runner, "n_static", 0)
    outs, x = [], np.asarray(x_raw, np.float32)
    for _ in range(steps):
        xe = runner.x_normalizer.encode(x[None])
        with jax.default_matmul_precision("highest"):
            y = np.asarray(fwd(params, xe))
        y_raw = runner.y_normalizer.decode(y)[0]
        outs.append(y_raw)
        fb = runner.feedback(y_raw)
        # with static geomodel channels, feedback evolves only the dynamic
        # channels — the geomodel persists (mirrors FNORunner.step)
        x = np.concatenate([x[:n_static], fb], axis=0) if n_static else fb
    return outs


# --verify tolerance. Served and reference outputs are both float32 end
# to end; they differ only in summation order (fused vs unfused mix,
# sharded vs serial FFTs, host vs device static spectra), which float32
# (eps 1.2e-7) bounds near 1e-6 of the field's scale after four blocks and
# the rollout feedback. VERIFY_RTOL leaves ~100x margin above that and is
# ~20x below the ~2e-3 relative error of one bf16 rounding (8-bit
# mantissa), so a bf16 pass anywhere in the served path fails it.
VERIFY_RTOL = 1e-4


def check_close(got: np.ndarray, expected: np.ndarray) -> float:
    """Raise unless ``got`` matches ``expected`` to VERIFY_RTOL, per element
    and against the field's scale (so elements near zero are judged by the
    output's magnitude, not by an absolute number in its physical units);
    returns the max abs difference."""
    np.testing.assert_allclose(
        got, expected, rtol=VERIFY_RTOL,
        atol=VERIFY_RTOL * float(np.abs(expected).max()),
    )
    return float(np.abs(got - expected).max())


def serve(runner, requests, max_slots: int, max_steps: int):
    """(finished, seconds, scheduler) for one serving pass over
    ``requests``. Callers must check ``sched.failed`` / the served count
    (``check_served``) — a scenario that fails admission is REPORTED, not
    an excuse to crash downstream."""
    from repro.serve import Scheduler

    sched = Scheduler(runner, max_slots)
    for r in requests:
        sched.submit(r)
    t0 = time.perf_counter()
    done = sched.run_until_done(max_steps=max_steps)
    dt = time.perf_counter() - t0
    return done, dt, sched


def check_served(done, requests, failed):
    """Exit nonzero with the per-request errors when the ensemble did not
    fully serve. An all-failed ensemble (e.g. a wrong --static-channels /
    --rollout-steps makes every admit raise) must report each admit error
    and exit — not crash on an empty latency list."""
    for r in failed:
        print(f"scenario rid={r.rid} FAILED: {r.error}", file=sys.stderr)
    if failed:
        raise SystemExit(
            f"{len(failed)}/{len(requests)} scenario(s) failed "
            f"(errors above); {len(done)} served"
        )
    if len(done) != len(requests):
        raise SystemExit(
            f"served {len(done)}/{len(requests)} scenarios; "
            f"raise --max-steps"
        )


def main(argv=None):
    """Serve the ensemble; returns a summary (``served``, ``compile_s``,
    ``verify_max_abs`` when --verify ran, and the ``runners``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True,
                    help="train.py --mode fno checkpoint directory")
    ap.add_argument("--scenarios", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4, help="scheduler slots")
    ap.add_argument("--rollout-steps", type=int, default=1,
                    help="autoregressive surrogate applications per scenario")
    ap.add_argument("--wells", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=None,
                    help="simulated host devices (CPU); default: all visible")
    ap.add_argument("--model-shards", type=int, nargs="+", default=None,
                    help="serving-mesh model parallelism; default: the "
                    "layout recorded in the checkpoint's fno_config.json")
    ap.add_argument("--max-steps", type=int, default=10000)
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas behind the gateway; each is an "
                    "independent FNORunner + scheduler restored from the "
                    "same checkpoint (1 = the pre-gateway single-scheduler "
                    "path, bit-identical to earlier releases)")
    ap.add_argument("--policy", default="affinity",
                    choices=("least-pending", "round-robin", "affinity"),
                    help="gateway routing policy (--replicas > 1): "
                    "backlog-aware least-pending, cyclic round-robin, or "
                    "geomodel cache-affinity with least-pending fallback")
    ap.add_argument("--ensemble", action="store_true",
                    help="UQ-ensemble mode: every scenario shares the same "
                    "geomodel (static channels), only well locations vary; "
                    "serves through the content-hash geomodel cache and "
                    "reports its hit-rate")
    ap.add_argument("--static-channels", type=int, default=1,
                    help="ensemble mode: leading input channels that are "
                    "the static geomodel (a --geomodel datagen store "
                    "trains a 2-channel model -> 1 static channel)")
    ap.add_argument("--cache-bytes", type=int, default=256 << 20,
                    help="geomodel-cache byte budget (LRU beyond it)")
    ap.add_argument("--cache-level", default="deep",
                    choices=("prelift", "deep"),
                    help="ensemble cache depth: 'prelift' stops at the "
                    "encoder lift; 'deep' (default) also caches the first "
                    "block's static kept-mode spectra + weight-mixed "
                    "contribution and serves the deep-split forward")
    ap.add_argument("--cache-store", default=None,
                    help="fleet-shared cache store replicas consult on "
                    "local miss: 'dict' for an in-process shared dict, or "
                    "a directory path for a file-backed (.npz) store that "
                    "persists across runs")
    ap.add_argument("--dup", type=int, default=1,
                    help="submit each scenario this many times (identical "
                    "in-flight requests dedup onto one slot)")
    ap.add_argument("--verify", action="store_true",
                    help="check every served output against the serial "
                    "fno_forward oracle (exit nonzero on mismatch)")
    ap.add_argument("--bench-sequential", action="store_true",
                    help="also serve one-at-a-time and report the "
                    "continuous-batching speedup")
    ap.add_argument("--reference", action="store_true",
                    help="time the numerical simulator on one scenario for "
                    "the surrogate-vs-simulator speedup")
    ap.add_argument("--use-pallas", action="store_true", default=None,
                    help="serve through the fused Pallas spectral path "
                    "(plane-cached weights); default: whatever the "
                    "checkpoint's fno_config.json recorded")
    ap.add_argument("--comm-chunks", type=int, default=None,
                    help="channel-chunked all-to-all overlap for the dist "
                    "forward; default: the checkpoint's recorded value")
    args = ap.parse_args(argv)

    from repro.common.compile_cache import enable_compile_cache
    from repro.serve import FNORunner, Gateway, open_cache_store

    enable_compile_cache()
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    n_static = args.static_channels if args.ensemble else 0
    # one store shared by every replica — that is the point of the tier
    store = (
        open_cache_store(args.cache_store)
        if args.cache_store and n_static else None
    )

    def load_runner():
        return FNORunner.from_checkpoint(
            args.ckpt_dir,
            model_shards=args.model_shards,
            max_slots=args.max_batch,
            n_static=n_static,
            cache_bytes=args.cache_bytes,
            cache_level=args.cache_level,
            cache_store=store,
            use_pallas=args.use_pallas,
            comm_chunks=args.comm_chunks,
        )

    try:
        runners = [load_runner() for _ in range(args.replicas)]
    except ValueError as e:  # library error -> CLI-flag wording
        raise SystemExit(f"--devices/--model-shards/--static-channels: {e}") from None
    runner = runners[0]
    cfg = runner.cfg
    print(
        f"serving {cfg.grid} FNO (width {cfg.width}, {cfg.n_blocks} blocks) "
        f"from step {runner.restored_step} on mesh "
        f"{dict(runner.mesh.shape)} (buckets {runner.buckets}"
        + (f", {args.replicas} replicas policy={args.policy})"
           if args.replicas > 1 else ")")
    )
    compile_s = sum(r.warmup() for r in runners)

    requests, sim_cfg = build_scenarios(
        cfg, args.scenarios, args.wells, args.seed, args.rollout_steps,
        n_static=n_static, dup=args.dup,
    )
    if args.replicas == 1:
        # the pre-gateway path, untouched: one scheduler, bit-identical
        done, dt, sched = serve(runner, requests, args.max_batch, args.max_steps)
        check_served(done, requests, sched.failed)
        engine_steps = sched.steps
        dedup_attached = sched.dedup_attached
        fleet_stats = None
    else:
        gateway = Gateway(runners, policy=args.policy)
        for r in requests:
            gateway.submit(r)
        t0 = time.perf_counter()
        done = gateway.run_until_done(max_steps=args.max_steps)
        dt = time.perf_counter() - t0
        check_served(done, requests, gateway.failed)
        stats = gateway.stats()
        fleet_stats = stats["fleet"]
        engine_steps = fleet_stats["ticks"]
        dedup_attached = fleet_stats["dedup_attached"]
        for rs in stats["replicas"]:
            print(
                f"  replica {rs['name']}: routed {rs['routed']}, served "
                f"{rs['finished']}, backlog {rs['pending']}, healthy "
                f"{rs['healthy']}"
                + (f", cache hit-rate {rs['cache']['hit_rate']:.3f} "
                   f"({rs['cache']['bytes'] / 1e6:.2f} MB)"
                   if rs["cache"] else "")
            )
    lat = sorted(r.finished_s - r.submitted_s for r in done)
    n = len(done)
    forwards = sum(r.batched_steps for r in runners)
    if n:
        print(
            f"served {n} scenarios x {args.rollout_steps} rollout step(s) in "
            f"{dt:.3f}s ({n / dt:.2f} scen/s, compile {compile_s:.2f}s excluded) "
            f"over {engine_steps} engine steps / {forwards} forwards; "
            f"latency p50 {lat[n // 2] * 1e3:.1f}ms p95 "
            f"{lat[min(n - 1, int(n * 0.95))] * 1e3:.1f}ms"
        )
    if args.replicas == 1 and runner.cache is not None:
        s = runner.cache.stats
        lv = s["level_bytes"]
        print(
            f"geomodel cache: hit-rate {s['hit_rate']:.3f} "
            f"({s['hits']} hits / {s['misses']} misses, {s['entries']} "
            f"entries, {s['bytes'] / 1e6:.2f} MB, {s['evictions']} evicted, "
            f"{s['deep_evictions']} deep-evicted); level MB "
            + "/".join(f"{lv[k] / 1e6:.2f}" for k in lv)
            + f" ({'/'.join(lv)}); dedup attached {dedup_attached} follower(s)"
        )
    elif fleet_stats is not None and (
        fleet_stats["cache_hits"] + fleet_stats["cache_misses"]
    ):
        print(
            f"fleet geomodel cache: hit-rate "
            f"{fleet_stats['cache_hit_rate']:.3f} "
            f"({fleet_stats['cache_hits']} hits / "
            f"{fleet_stats['cache_misses']} misses across "
            f"{fleet_stats['n_replicas']} replicas, "
            f"{fleet_stats['cache_bytes'] / 1e6:.2f} MB); dedup attached "
            f"{dedup_attached} follower(s)"
        )
    if store is not None:
        ss = store.stats
        print(
            f"cache store: {ss['hits']} hits / {ss['misses']} misses "
            f"({ss['hit_rate']:.3f}), {ss['puts']} puts, {ss['entries']} "
            f"entries, {ss['bytes'] / 1e6:.2f} MB"
        )

    if args.bench_sequential:
        seq_requests, _ = build_scenarios(
            cfg, args.scenarios, args.wells, args.seed, args.rollout_steps,
            n_static=n_static, dup=args.dup,
        )
        seq_done, seq_dt, seq_sched = serve(runner, seq_requests, 1, args.max_steps)
        check_served(seq_done, seq_requests, seq_sched.failed)
        speedup = seq_dt / dt
        print(
            f"sequential: {len(seq_done)} scenarios in {seq_dt:.3f}s "
            f"({len(seq_done) / seq_dt:.2f} scen/s); continuous batching "
            f"speedup {speedup:.2f}x"
        )

    summary = {"served": n, "compile_s": compile_s, "runners": runners}
    if args.verify:
        worst = 0.0
        for r in done:
            expected = oracle_rollout(runner, r.x, args.rollout_steps)
            for got, exp in zip(r.outputs, expected):
                worst = max(worst, check_close(got, exp))
        summary["verify_max_abs"] = worst
        print(f"verify OK: {n} scenarios match the serial oracle "
              f"(max abs diff {worst:.2e})")

    if args.reference:
        from repro.data.pde.two_phase import simulate_task

        t0 = time.perf_counter()
        simulate_task(args.seed, args.wells, sim_cfg.grid, cfg.grid[3])
        sim_s = time.perf_counter() - t0
        per_scen = dt / n
        print(
            f"reference simulator: {sim_s:.2f}s/scenario vs surrogate "
            f"{per_scen * 1e3:.1f}ms/scenario -> {sim_s / per_scen:.0f}x "
            f"(paper reports ~1e5x at Sleipner scale on real accelerators)"
        )
    return summary


if __name__ == "__main__":
    # before jax starts its backend: --devices sizes the CPU backend
    apply_device_flag(sys.argv)
    main()
