"""End-to-end training driver (CPU-sized by default; mesh-ready).

Two modes:
  fno — train the paper's FNO surrogate on simulated data (from a chunked
        ArrayStore produced by the cloud datagen layer, or synthetic);
  lm  — train a reduced-config assigned architecture on synthetic tokens.

The fno path is fully sharded end to end: batches come from the
``ShardedDatasetLoader`` (each device reads only the store chunks under its
``(mx, my)`` pencil and its slice of the batch dim, prefetched on a
background thread) and the jitted step goes through ``shard_train_step``
with explicit batch/param shardings on the data x model mesh — the same
PartitionSpecs on both sides, so no resharding happens at the jit boundary.

Fault tolerance is on by default: periodic sharded checkpoints, restart
from the latest on crash (--inject-fault demonstrates it), straggler
watchdog. ``--devices N`` spawns N host devices for a real data-parallel
mesh on CPU.
"""
import argparse
import dataclasses
import functools
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs import (
    FNO_IDS, fno_with_overrides, get_arch, parse_fno_overrides, reduced,
)
from repro.core import FNOConfig, forward_and_specs, init_params, mse_loss
from repro.launch.devices import apply_device_flag
from repro.launch.devices import sniff_devices  # noqa: F401  (re-export)
from repro.launch.mesh import build_fno_mesh
from repro.models import init_lm_params, lm_loss
from repro.models.policy import LOCAL
from repro.train import AdamWConfig, init_opt_state, make_train_step, warmup_cosine
from repro.train.fault import FaultInjector, run_supervised
from repro.train.train_loop import shard_train_step, train_state_shardings


def start_online_datagen(args):
    """Spawn ``run_datagen`` in a background thread (the paper's 'simulate
    in advance' cost removed: training overlaps it). Returns
    ``(thread, err_holder)``; the holder carries any datagen exception so
    the trainer fails loudly instead of stalling forever."""
    from repro.launch.datagen import build_parser, run_datagen

    if args.x_store:
        root = os.path.dirname(os.path.abspath(args.x_store))
        if (
            os.path.dirname(os.path.abspath(args.y_store)) != root
            or os.path.basename(os.path.abspath(args.x_store)) != "x"
            or os.path.basename(os.path.abspath(args.y_store)) != "y"
        ):
            raise SystemExit(
                "--online: stores must be <root>/x and <root>/y "
                "(datagen's layout); or pass --out <root> instead"
            )
    elif args.out:
        root = args.out
        args.x_store = os.path.join(root, "x")
        args.y_store = os.path.join(root, "y")
    else:
        raise SystemExit("--online needs --out (or --x-store/--y-store)")
    nx, ny, nz, nt = args.grid
    # same pre-parsed argv contract as the CLI (and the same --devices
    # parsing caveat does not apply: datagen never touches jax/XLA flags)
    dg_args = build_parser().parse_args([
        "--pde", args.pde, "--n", str(args.n_data),
        "--grid", str(nx), str(ny), str(nz), "--nt", str(nt),
        "--out", root, "--backend", args.datagen_backend,
        "--workers", str(args.datagen_workers),
        "--chunks-xy", str(args.chunks_xy[0]), str(args.chunks_xy[1]),
        "--stats-every", str(max(1, min(args.batch, 4))),
        "--seed", str(args.seed), "--resume",
    ])
    err = []

    def _run():
        try:
            run_datagen(dg_args)
        except BaseException as e:  # noqa: BLE001 — surfaced by the waiters
            err.append(e)

    th = threading.Thread(target=_run, name="online-datagen", daemon=True)
    th.start()
    return th, err


def _wait_online(path: str, err: list, timeout: float, need_stats: bool):
    """Block until the store exists (and, if asked, carries normalization
    stats from the incremental Welford pass); returns the opened store."""
    from repro.data import ArrayStore

    deadline = time.monotonic() + timeout
    while True:
        if os.path.exists(os.path.join(path, "meta.json")):
            store = ArrayStore.open(path)
            if not need_stats or "stats" in store.meta:
                return store
        if err:
            raise RuntimeError("online datagen failed") from err[0]
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"--online: store {path} "
                f"{'has no stats' if need_stats else 'never appeared'} "
                f"after {timeout}s"
            )
        time.sleep(0.05)


def synthetic_fno_data(cfg: FNOConfig, n: int, seed: int = 0,
                       geomodel: bool = False):
    """Band-limited random fields (stand-in when no simulated store given).

    ``geomodel`` lays the inputs out as a ``datagen --geomodel`` store does:
    channel 0 is the shared log-permeability geomodel (the static channel
    the serving cache keys on), the others are seeded injection-well maps;
    the target is then a transform of the well channels."""
    nx, ny, nz, nt = cfg.grid
    if geomodel:
        from repro.data.pde.two_phase import TwoPhaseConfig, random_well_mask
        from repro.launch.datagen import geomodel_channel

        if cfg.in_channels < 2:
            raise SystemExit("--geomodel needs in_channels >= 2")
        sim_cfg = TwoPhaseConfig(grid=(nx, ny, nz))
        geo = geomodel_channel((nx, ny, nz), nt)
        wells = np.stack([
            np.repeat(random_well_mask(sim_cfg, 2, seed + i)[None, ..., None],
                      cfg.in_channels - 1, axis=0).repeat(nt, axis=-1)
            for i in range(n)
        ])
        x = np.concatenate([np.repeat(geo[None], n, axis=0), wells], axis=1)
        src = jnp.asarray(wells)
    else:
        k1, _ = jax.random.split(jax.random.PRNGKey(seed))
        x = src = jax.random.normal(
            k1, (n, cfg.in_channels, nx, ny, nz, nt), jnp.float32
        )
    # target: smoothed nonlinear transform (learnable mapping)
    y = jnp.tanh(jnp.roll(src, 1, axis=2) + 0.5 * jnp.roll(src, 2, axis=3)) * 0.5
    return np.asarray(x, np.float32), np.asarray(y[:, : cfg.out_channels])


def fno_config_from_args(args, store_grid=None, store_channels=None):
    """The run's FNOConfig: a named config with explicit overrides
    (``--config``/``--override``), or the small ``--grid``/``--width``
    model. A store, when given, must agree with the named config."""
    if args.config is None:
        grid = tuple(store_grid or args.grid or (16, 16, 8, 8))
        in_ch, out_ch = store_channels or (1, 1)
        return FNOConfig(
            grid=grid,
            modes=tuple(max(2, g // 4) for g in grid),
            width=args.width or 8,
            in_channels=in_ch,
            out_channels=out_ch,
            n_blocks=4,
            decoder_dim=32,
            use_pallas=args.use_pallas,
            comm_chunks=args.comm_chunks,
        )
    if args.grid is not None or args.width is not None:
        raise SystemExit(
            "--config takes its sizes from the named config; change them "
            "with --override grid=... / width=..."
        )
    cfg = fno_with_overrides(args.config, args.overrides)
    if store_grid is not None and (
        tuple(store_grid) != cfg.grid
        or tuple(store_channels) != (cfg.in_channels, cfg.out_channels)
    ):
        raise SystemExit(
            f"store grid {tuple(store_grid)} / channels {store_channels} "
            f"disagree with {args.config} {cfg.grid} / "
            f"{(cfg.in_channels, cfg.out_channels)}"
        )
    return dataclasses.replace(
        cfg, use_pallas=args.use_pallas, comm_chunks=args.comm_chunks
    )


def write_fno_serving_config(ckpt_dir: str, cfg: FNOConfig, args, x_src, y_src,
                             normalized) -> None:
    """Persist the serving contract next to the checkpoints: architecture,
    model-shard layout, and a snapshot of the normalization stats/kind the
    run trained with — everything ``FNORunner.from_checkpoint`` needs to
    serve the surrogate in physical units without the original stores."""
    def stats_of(src):
        return (getattr(src, "meta", None) or {}).get("stats")

    def kind_of(src):
        return (getattr(src, "meta", None) or {}).get("normalizer", "meanstd")

    os.makedirs(ckpt_dir, exist_ok=True)
    payload = {
        # the named config and its overrides, when the run started from one
        "config": args.config,
        "overrides": {
            k: list(v) if isinstance(v, tuple) else v
            for k, v in (args.overrides or {}).items()
        },
        "grid": list(cfg.grid),
        "modes": list(cfg.modes),
        "width": cfg.width,
        "in_channels": cfg.in_channels,
        "out_channels": cfg.out_channels,
        "n_blocks": cfg.n_blocks,
        "decoder_dim": cfg.decoder_dim,
        "model_shards": list(args.model_shards),
        "use_pallas": cfg.use_pallas,
        "comm_chunks": cfg.comm_chunks,
        "normalized": list(normalized),
        "normalizer": kind_of(x_src),
        "x_stats": stats_of(x_src),
        "y_stats": stats_of(y_src),
    }
    tmp = os.path.join(ckpt_dir, f"fno_config.json.tmp{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.rename(tmp, os.path.join(ckpt_dir, "fno_config.json"))


def compile_report(jit_step, abstract_params, cfg: FNOConfig, batch: int) -> dict:
    """Compile the fno train step ahead of the run (the run's own call then
    finds it in the persistent cache) and print what the device program
    needs: compile seconds and ``memory_analysis`` bytes."""
    xb = jax.ShapeDtypeStruct((batch, cfg.in_channels) + cfg.grid, jnp.float32)
    yb = jax.ShapeDtypeStruct((batch, cfg.out_channels) + cfg.grid, jnp.float32)
    t0 = time.perf_counter()
    compiled = jit_step.lower(
        abstract_params, jax.eval_shape(init_opt_state, abstract_params),
        {"x": xb, "y": yb},
    ).compile()
    rep = {"compile_s": time.perf_counter() - t0}
    mem = compiled.memory_analysis()
    for k in ("argument", "output", "alias", "temp"):
        rep[f"{k}_bytes"] = int(getattr(mem, f"{k}_size_in_bytes"))
    rep["peak_bytes"] = int(mem.peak_memory_in_bytes)
    print(
        f"compile: train step {rep['compile_s']:.1f}s; compiled bytes "
        + " ".join(f"{k} {rep[k + '_bytes'] / 1e9:.2f} GB"
                   for k in ("argument", "output", "alias", "temp", "peak")),
        flush=True,
    )
    return rep


def main(argv=None):
    """Train; returns the supervisor's result, whose ``info`` carries the
    run's config and, with ``--compile-report``, the step's compile time
    and compiled bytes."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("fno", "lm"), default="fno")
    ap.add_argument("--arch", default="gemma-7b", help="lm mode: assigned arch id")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--inject-fault", type=int, default=None, help="fail once at this step")
    ap.add_argument("--x-store", default=None)
    ap.add_argument("--y-store", default=None)
    ap.add_argument("--online", action="store_true",
                    help="fno mode: spawn datagen in the background and "
                    "start training from the store's visible sample prefix "
                    "(Meyer-et-al streaming) instead of simulate-then-train")
    ap.add_argument("--out", default=None,
                    help="--online: dataset root (writes <out>/x, <out>/y); "
                    "alternative to --x-store/--y-store")
    ap.add_argument("--pde", choices=("two_phase", "navier_stokes"),
                    default="two_phase", help="--online: PDE to simulate")
    ap.add_argument("--datagen-workers", type=int, default=4)
    ap.add_argument("--datagen-backend", choices=("process", "thread"),
                    default="thread")
    ap.add_argument("--chunks-xy", type=int, nargs=2, default=(2, 2),
                    metavar=("CX", "CY"), help="--online: store chunking")
    ap.add_argument("--online-timeout", type=float, default=600.0,
                    help="--online: max seconds to wait for the simulator "
                    "(first samples, stats, per-step back-pressure)")
    ap.add_argument("--no-normalize", action="store_true",
                    help="skip input normalization from the store's stats")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the loader's background prefetch thread")
    ap.add_argument("--no-shuffle", action="store_true")
    ap.add_argument("--grid", type=int, nargs=4, default=None,
                    help="fno mode without --config (default 16 16 8 8)")
    ap.add_argument("--width", type=int, default=None,
                    help="fno mode without --config (default 8)")
    ap.add_argument("--config", choices=FNO_IDS, default=None,
                    help="fno mode: start from a named FNO config "
                    "(configs.get_fno) at its published sizes")
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="--config: replace one config field, e.g. "
                    "grid=64,16,24,88 or modes=24,2,2,10 (repeatable); "
                    "recorded in fno_config.json")
    ap.add_argument("--geomodel", action="store_true",
                    help="fno mode, synthetic data: channel 0 is the shared "
                    "geomodel (static serving channel), the rest well maps")
    ap.add_argument("--compile-report", action="store_true",
                    help="compile the train step ahead of the run and "
                    "print its compile seconds and compiled bytes")
    ap.add_argument("--n-data", type=int, default=16)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--model-shards", type=int, nargs="+", default=[1],
        help="fno mode: model-parallel shards. One value P shards the "
        "solution along x (paper Alg. 2); two values PX PY use the 2-D "
        "pencil decomposition on a ('mx','my') mesh.",
    )
    ap.add_argument(
        "--use-pallas", action="store_true",
        help="fno mode: fused Pallas spectral path (truncate + channel-mix "
        "+ pad in one kernel pass; interpret mode on CPU). Equivalence-"
        "gated vs the unfused path; persisted into fno_config.json so "
        "serving defaults to the same path.",
    )
    ap.add_argument(
        "--comm-chunks", type=int, default=1,
        help="fno mode: channel-chunk the distributed FFT pipelines so "
        "each chunk's all-to-all overlaps the next chunk's FFTs "
        "(bit-identical; the overlap needs a latency-hiding scheduler).",
    )
    args = ap.parse_args(argv)
    try:
        args.overrides = parse_fno_overrides(args.override)
    except ValueError as e:
        raise SystemExit(f"--override: {e}") from None
    if args.overrides and args.config is None:
        raise SystemExit("--override needs --config")
    enable_compile_cache()

    opt_cfg = AdamWConfig(
        lr=warmup_cosine(args.lr, warmup=10, total=args.steps), weight_decay=0.0
    )
    loader = None
    schedule = None
    dg_thread = dg_err = None
    if args.online and args.mode != "fno":
        raise SystemExit("--online is an fno-mode flag")

    if args.mode == "fno":
        from repro.data import (
            ArrayStore, NdArraySource, ShardedDatasetLoader, StreamingSchedule,
        )

        if args.online:
            dg_thread, dg_err = start_online_datagen(args)
            x_src = _wait_online(
                args.x_store, dg_err, args.online_timeout,
                need_stats=not args.no_normalize,
            )
            y_src = _wait_online(
                args.y_store, dg_err, args.online_timeout, need_stats=False
            )
        else:
            if bool(args.x_store) != bool(args.y_store):
                raise SystemExit("--x-store and --y-store must be given together")
            if args.x_store:
                x_src = ArrayStore.open(args.x_store)
                y_src = ArrayStore.open(args.y_store)
            else:
                x_src = y_src = None
        if x_src is not None:
            if args.geomodel:
                raise SystemExit("--geomodel shapes synthetic data; a "
                                 "--geomodel datagen store already has it")
            cfg = fno_config_from_args(
                args, x_src.shape[-4:], (x_src.shape[1], y_src.shape[1])
            )
        else:
            cfg = fno_config_from_args(args)
        if x_src is None:
            x_all, y_all = synthetic_fno_data(
                cfg, args.n_data, seed=args.seed, geomodel=args.geomodel
            )
            x_src, y_src = NdArraySource(x_all), NdArraySource(y_all)

        try:
            mesh, model_axis, n_model = build_fno_mesh(
                args.devices, args.model_shards
            )
        except ValueError as e:  # library error -> CLI-flag wording
            raise SystemExit(f"--devices/--model-shards: {e}") from None
        n_dp = mesh.shape["data"]
        if args.batch % n_dp:
            raise SystemExit(
                f"--batch {args.batch} not divisible by the data-parallel "
                f"size {n_dp} ({args.devices} devices / {n_model} model shards)"
            )
        # one source of truth for the model/data layout, shared with the
        # serving runner: the loader assembles batches with exactly the
        # specs the jitted step declares
        fwd, x_spec, p_specs = forward_and_specs(
            mesh, cfg, dp_axes=("data",), model_axis=model_axis
        )

        def loss_fn(params, batch):
            pred = fwd(params, batch["x"])
            return mse_loss(pred, batch["y"]), {}

        batch_specs = {"x": x_spec, "y": x_spec}
        init_fn = functools.partial(init_params, cfg=cfg)
        if args.online:
            # draw each batch from the complete-prefix watermark while
            # datagen is still writing; the per-step watermark log is
            # persisted next to the checkpoints so a restarted process
            # replays the exact same schedule (fault supervisor contract)
            os.makedirs(args.ckpt_dir, exist_ok=True)
            if not args.no_normalize:
                # datagen keeps rewriting meta.json stats as samples land;
                # snapshot the stats this run normalizes with so a restarted
                # process replays numerically identical batches, not just
                # the same sample ids
                snap = os.path.join(args.ckpt_dir, "stats_snapshot.json")
                if os.path.exists(snap):
                    with open(snap) as f:
                        x_src.meta["stats"] = json.load(f)
                else:
                    tmp = snap + f".tmp{os.getpid()}"
                    with open(tmp, "w") as f:
                        json.dump(x_src.meta["stats"], f)
                    os.rename(tmp, snap)
            schedule = StreamingSchedule(
                [x_src, y_src],
                args.batch,
                seed=args.seed,
                timeout=args.online_timeout,
                log_path=os.path.join(args.ckpt_dir, "watermarks.json"),
            )
        # persist the serving contract (arch + normalization snapshot —
        # AFTER the online path pinned its stats snapshot) so serve_pde.py /
        # FNORunner.from_checkpoint can load this run without the stores
        write_fno_serving_config(
            args.ckpt_dir, cfg, args, x_src, y_src,
            normalized=() if args.no_normalize else ("x",),
        )
        loader = ShardedDatasetLoader(
            {"x": x_src, "y": y_src},
            mesh,
            args.batch,
            batch_specs,
            seed=args.seed,
            shuffle=not args.no_shuffle,
            normalize=() if args.no_normalize else ("x",),
            prefetch=0 if args.no_prefetch else 2,
            schedule=schedule,
        )
        batches = loader.batch
    else:
        from repro.core.partition import make_mesh

        cfg = reduced(get_arch(args.arch))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab, size=(args.n_data, args.batch, 33), dtype=np.int32)

        def loss_fn(params, batch):
            loss, m = lm_loss(params, batch, cfg, LOCAL)
            return loss, m

        def batches(step):
            t = tokens[step % args.n_data]
            return {"tokens": jnp.asarray(t[:, :-1]), "targets": jnp.asarray(t[:, 1:])}

        init_fn = functools.partial(init_lm_params, cfg=cfg)
        if args.batch % args.devices:
            raise SystemExit(
                f"--batch {args.batch} not divisible by --devices {args.devices}"
            )
        mesh = make_mesh((args.devices,), ("data",))
        from jax.sharding import PartitionSpec as P

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        p_specs = jax.tree.map(lambda _: P(), abstract)
        batch_specs = {"tokens": P("data"), "targets": P("data")}

    step_fn = make_train_step(loss_fn, opt_cfg, grad_accum=args.grad_accum)
    abstract_params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    jit_step = shard_train_step(
        step_fn, mesh, p_specs, abstract_params, batch_specs, dp_axes=("data",)
    )

    state_shardings = train_state_shardings(
        mesh, p_specs, abstract_params, dp_axes=("data",)
    )

    @functools.partial(jax.jit, out_shardings=state_shardings)
    def init_state():
        # built in place on the mesh: a model-parallel state may not fit
        # whole on one device
        params = init_fn(jax.random.PRNGKey(0))
        return {"params": params, "opt": init_opt_state(params)}

    info = {"config": args.config, "overrides": args.overrides}
    if args.compile_report and args.mode == "fno":
        info.update(compile_report(jit_step, abstract_params, cfg, args.batch))

    online_info = {}

    def train_step(state, batch):
        if schedule is not None and "first_n_complete" not in online_info:
            # the moment the first step launches: how much of the dataset
            # exists? < n proves simulation and training truly overlap
            online_info["first_visible"] = schedule.visible_now()
            online_info["first_n_complete"] = loader.sources["x"].n_complete()
        params, opt, metrics = jit_step(state["params"], state["opt"], batch)
        return {"params": params, "opt": opt}, metrics

    injector = FaultInjector([args.inject_fault]) if args.inject_fault is not None else None
    try:
        result = run_supervised(
            init_state=init_state,
            train_step=train_step,
            batch_iter=batches,
            total_steps=args.steps,
            ckpt_dir=args.ckpt_dir,
            save_every=args.save_every,
            injector=injector,
            shardings=state_shardings,
            async_save=True,
        )
    finally:
        if loader is not None:
            loader.close()
    if dg_thread is not None:
        dg_thread.join()  # let the simulator finish/flush before reporting
        if dg_err:
            raise RuntimeError("online datagen failed") from dg_err[0]
    result.info.update(info)
    first = result.metrics_log[0][1]["loss"] if result.metrics_log else float("nan")
    last = result.metrics_log[-1][1]["loss"] if result.metrics_log else float("nan")
    print(
        f"done: steps={result.final_step} failures={result.failures} "
        f"restores={result.restores} loss {first:.3e} -> {last:.3e} "
        f"stragglers={len(result.straggler_steps)}"
    )
    if schedule is not None:
        n_total = loader.sources["x"].shape[0]
        sm = schedule.metrics()
        overlapped = online_info.get("first_n_complete", n_total) < n_total
        print(
            f"online: first step with {online_info.get('first_n_complete', '?')}"
            f"/{n_total} samples complete "
            f"(visible={online_info.get('first_visible', '?')}) "
            f"stalls={sm['stalls']} stall_s={sm['stall_s']} "
            f"overlap={overlapped}"
        )
    return result


if __name__ == "__main__":
    # before jax starts its backend: --devices sizes the CPU backend
    apply_device_flag(sys.argv)
    main()
