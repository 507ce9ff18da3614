"""Multi-pod AOT dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON artifact with:
  * compiled.memory_analysis()  -> per-device bytes (proves it fits)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes accessed
  * parsed collective traffic   -> bytes on the ICI wire per device
  * analytic MODEL_FLOPS        -> 6·N·D (train) or 2·N·D (serve)
EXPERIMENTS.md §Dry-run / §Roofline are generated from these artifacts.
"""
import argparse
import dataclasses
import functools
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.tree import tree_params
from repro.configs import (
    ARCH_IDS,
    FNO_IDS,
    LM_SHAPES,
    cell_supported,
    get_arch,
    get_fno,
    get_fno_model_axes,
    get_shape,
    input_specs,
)
from repro.core import fno as fno_lib
from repro.launch import hlo_analysis
from repro.launch.mesh import dp_axes_for, make_pencil_mesh, make_production_mesh
from repro.models import transformer as tf_lib
from repro.models import whisper as wh_lib
from repro.models.policy import ParallelPolicy
from repro.train import AdamWConfig, init_opt_state, make_train_step
from repro.train.optimizer import opt_state_specs


def _safe(spec: P, shape, mesh) -> P:
    """Drop axes that don't divide the dim (e.g. batch 1 at long_500k)."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for d, ax in zip(shape, dims):
        if ax is None:
            out.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(ax if d % size == 0 else None)
    return P(*out)


def _ns(mesh, spec_tree, abstract_tree):
    return jax.tree.map(
        lambda s, a: NamedSharding(mesh, _safe(s if isinstance(s, P) else P(), a.shape, mesh)),
        spec_tree,
        abstract_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Cell builders: return (jitted_fn, example_args) ready for .lower().
# ---------------------------------------------------------------------------

def build_lm_cell(arch_id: str, shape_name: str, mesh, *, seq_shard=False, moe_a2a=True, kv_quant=False):
    cfg = get_arch(arch_id)
    shape = get_shape(shape_name)
    dp = dp_axes_for(mesh)
    policy = ParallelPolicy(
        mesh=mesh, dp_axes=dp, model_axis="model", seq_shard=seq_shard,
        moe_a2a=moe_a2a, remat=True, unroll_decode=True, kv_quant=kv_quant,
    )
    key = jax.random.PRNGKey(0)
    is_whisper = cfg.family == "encdec"

    if is_whisper:
        abstract_params = jax.eval_shape(functools.partial(wh_lib.init_whisper_params, cfg=cfg), key)
        p_specs = wh_lib.whisper_param_specs(cfg, policy)
    else:
        abstract_params = jax.eval_shape(functools.partial(tf_lib.init_lm_params, cfg=cfg), key)
        p_specs = tf_lib.param_specs(cfg, policy)
    shape_cfg = get_shape(shape_name)
    if shape_cfg.kind != "train":
        # Serving runs on bf16 weights (the f32 master copies live in the
        # training job, not the server).
        abstract_params = jax.tree.map(
            lambda t: jax.ShapeDtypeStruct(
                t.shape, jnp.bfloat16 if t.dtype == jnp.float32 else t.dtype
            ),
            abstract_params,
        )
    params_sh = _ns(mesh, p_specs, abstract_params)

    ins = input_specs(cfg, shape)
    b, s = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        loss_fn = (
            (lambda p, batch: wh_lib.whisper_loss(p, batch, cfg, policy))
            if is_whisper
            else (lambda p, batch: tf_lib.lm_loss(p, batch, cfg, policy))
        )
        step = make_train_step(loss_fn, AdamWConfig(lr=3e-4, weight_decay=0.1))
        abstract_opt = jax.eval_shape(init_opt_state, abstract_params)
        o_specs = opt_state_specs(p_specs, abstract_params, mesh, dp, zero1=True)
        opt_sh = _ns(mesh, o_specs, abstract_opt)
        batch_specs = {"tokens": P(dp, None), "targets": P(dp, None)}
        if is_whisper:
            batch_specs["frames"] = P(dp, None, None)
        batch_sh = _ns(mesh, batch_specs, ins)
        jitted = jax.jit(
            step,
            in_shardings=(params_sh, opt_sh, batch_sh),
            out_shardings=(params_sh, opt_sh, None),
            donate_argnums=(0, 1),
        )
        return jitted, (abstract_params, abstract_opt, ins), cfg

    if shape.kind == "prefill":
        if is_whisper:
            fn = lambda p, tokens, frames: wh_lib.whisper_prefill(p, tokens, frames, cfg, policy)
            args_sh = (params_sh, NamedSharding(mesh, _safe(P(dp, None), (b, s), mesh)),
                       NamedSharding(mesh, _safe(P(dp, None, None), (b, cfg.encoder.frames, cfg.d_model), mesh)))
            args = (abstract_params, ins["tokens"], ins["frames"])
        else:
            fn = lambda p, tokens: tf_lib.lm_prefill(p, tokens, cfg, policy)
            args_sh = (params_sh, NamedSharding(mesh, _safe(P(dp, None), (b, s), mesh)))
            args = (abstract_params, ins["tokens"])
        jitted = jax.jit(fn, in_shardings=args_sh)
        return jitted, args, cfg

    # decode: one token against a cache of length seq_len
    if is_whisper:
        abstract_cache = jax.eval_shape(lambda: wh_lib.init_whisper_cache(cfg, b, s))
        c_specs = {
            "self": {"k": P(None, dp, None, None, None), "v": P(None, dp, None, None, None)},
            "cross_k": P(None, dp, None, None, None),
            "cross_v": P(None, dp, None, None, None),
        }
        fn = lambda p, t, c, i: wh_lib.whisper_decode_step(p, t, c, i, cfg, policy)
    else:
        abstract_cache = jax.eval_shape(lambda: tf_lib.init_cache(cfg, b, s, policy=policy))
        c_specs = tf_lib.cache_specs(cfg, policy)
        fn = lambda p, t, c, i: tf_lib.lm_decode_step(p, t, c, i, cfg, policy)
    cache_sh = _ns(mesh, c_specs, abstract_cache)
    tok_sh = NamedSharding(mesh, _safe(P(dp, None), (b, 1), mesh))
    jitted = jax.jit(
        fn,
        in_shardings=(params_sh, tok_sh, cache_sh, NamedSharding(mesh, P())),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
    )
    args = (
        abstract_params,
        jax.ShapeDtypeStruct((b, 1), jnp.int32),
        abstract_cache,
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    return jitted, args, cfg


def build_fno_cell(fno_id: str, shape_name: str, mesh, *, fno_dtype=None):
    cfg, shapes = get_fno(fno_id)
    if fno_dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=fno_dtype)
    shape = {name: (bsz, kind) for name, bsz, kind in shapes}[shape_name]
    bsz, kind = shape
    model_axis, pencil = get_fno_model_axes(fno_id)
    if isinstance(model_axis, tuple):
        # Pencil config: re-carve the production device pool into a
        # ("data", "mx", "my") mesh of the same total size so the lowered
        # HLO actually contains the 2-D schedule's two all-to-alls.
        px, py = pencil
        if mesh.size % (px * py):
            raise ValueError(
                f"{fno_id}: pencil {pencil} does not divide mesh size {mesh.size}"
            )
        mesh = make_pencil_mesh(mesh.size // (px * py), px, py)
    dp = dp_axes_for(mesh)
    key = jax.random.PRNGKey(0)
    abstract_params = jax.eval_shape(functools.partial(fno_lib.init_params, cfg=cfg), key)
    fwd, x_spec, p_specs = fno_lib.forward_and_specs(mesh, cfg, dp_axes=dp, model_axis=model_axis)
    params_sh = _ns(mesh, p_specs, abstract_params)
    nx, ny, nz, nt = cfg.grid
    x_abs = jax.ShapeDtypeStruct((bsz, cfg.in_channels, nx, ny, nz, nt), jnp.float32)
    y_abs = jax.ShapeDtypeStruct((bsz, cfg.out_channels, nx, ny, nz, nt), jnp.float32)
    x_sh = NamedSharding(mesh, _safe(x_spec, x_abs.shape, mesh))

    cell_meta = {"mesh": mesh}
    if kind == "infer":
        jitted = jax.jit(fwd, in_shardings=(params_sh, x_sh), out_shardings=x_sh)
        return jitted, (abstract_params, x_abs), cfg, cell_meta

    def loss_fn(p, batch):
        pred = fwd(p, batch["x"])
        return fno_lib.mse_loss(pred, batch["y"]), {}

    step = make_train_step(loss_fn, AdamWConfig(lr=1e-3))
    abstract_opt = jax.eval_shape(init_opt_state, abstract_params)
    o_specs = opt_state_specs(p_specs, abstract_params, mesh, dp, zero1=True)
    opt_sh = _ns(mesh, o_specs, abstract_opt)
    batch_sh = {"x": x_sh, "y": x_sh}
    jitted = jax.jit(
        step,
        in_shardings=(params_sh, opt_sh, batch_sh),
        out_shardings=(params_sh, opt_sh, None),
        donate_argnums=(0, 1),
    )
    return jitted, (abstract_params, abstract_opt, {"x": x_abs, "y": y_abs}), cfg, cell_meta


# ---------------------------------------------------------------------------
# Lower + compile + analyse one cell.
# ---------------------------------------------------------------------------

def model_flops_lm(cfg, shape) -> float:
    n_active = cfg.approx_active_params()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def model_flops_fno(cfg: fno_lib.FNOConfig, batch: int, kind: str) -> float:
    """Analytic forward FLOPs: spectral einsum + bypass + enc/dec + FFTs."""
    import math

    nx, ny, nz, nt = cfg.grid
    grid_pts = nx * ny * nz * nt
    k_modes = 1
    for m in cfg.mode_shape:
        k_modes *= m
    w = cfg.width
    spectral = 8.0 * w * w * k_modes          # complex MAC = 8 real flops
    bypass = 2.0 * w * w * grid_pts
    fft = 2 * 5.0 * grid_pts * w * (math.log2(nx) + math.log2(ny) + math.log2(nz) + math.log2(nt))
    per_block = spectral + bypass + fft
    enc = 2.0 * cfg.in_channels * w * grid_pts
    dec = 2.0 * w * cfg.decoder_dim * grid_pts + 2.0 * cfg.decoder_dim * cfg.out_channels * grid_pts
    fwd = batch * (enc + dec + cfg.n_blocks * per_block)
    return 3.0 * fwd if kind == "train" else fwd


def run_cell(
    kind: str,
    arch_id: str,
    shape_name: str,
    *,
    multi_pod: bool,
    out_dir: Optional[str],
    seq_shard: bool = False,
    fno_dtype=None,
    kv_quant: bool = False,
) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    if kind == "fno":
        jitted, args, cfg, cell_meta = build_fno_cell(arch_id, shape_name, mesh, fno_dtype=fno_dtype)
        # Pencil configs re-carve the mesh; record what was actually
        # lowered, not what was requested.
        mesh = cell_meta["mesh"]
        n_dev = mesh.size
        shape_kind = dict((n, k) for n, _, k in get_fno(arch_id)[1])[shape_name]
        mf = model_flops_fno(cfg, [b for n, b, _ in get_fno(arch_id)[1] if n == shape_name][0], shape_kind)
        n_params = tree_params(jax.eval_shape(functools.partial(fno_lib.init_params, cfg=cfg), jax.random.PRNGKey(0)))
    else:
        jitted, args, cfg = build_lm_cell(arch_id, shape_name, mesh, seq_shard=seq_shard, kv_quant=kv_quant)
        mf = model_flops_lm(cfg, get_shape(shape_name))
        n_params = cfg.approx_params()
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = hlo_analysis.collect_collectives(hlo, n_devices_default=n_dev)
    compute = hlo_analysis.collect_compute(hlo)

    artifact = {
        "arch": arch_id,
        "shape": shape_name,
        "kind": kind,
        "mesh": {"shape": list(mesh.devices.shape), "axes": list(mesh.axis_names), "devices": n_dev},
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "n_params": int(n_params),
        "model_flops": mf,
        # cost_analysis counts while bodies once; *_loopaware weights loop
        # bodies by their trip counts (see hlo_analysis.collect_compute).
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "hlo_flops_loopaware": compute["flops"],
        "hlo_bytes_est": compute["bytes_est"],
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            # CPU buffer assignment performs no reuse: temp is the SUM of
            # all temporaries, an upper bound on TPU live memory.
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_per_device": hlo_analysis.peak_memory_bytes(mem),
            "resident_bytes": int(
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        },
        "collectives": colls.to_dict(),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "multipod" if multi_pod else "pod"
        if not seq_shard:
            suffix += "_nosp"
        path = os.path.join(out_dir, f"{arch_id}_{shape_name}_{suffix}.json")
        with open(path, "w") as f:
            json.dump(artifact, f, indent=1)
        artifact["path"] = path
    return artifact


def iter_cells():
    for arch_id in ARCH_IDS:
        cfg = get_arch(arch_id)
        for shape in LM_SHAPES:
            ok, why = cell_supported(cfg, shape)
            if ok:
                yield ("lm", arch_id, shape.name)
    for fno_id in FNO_IDS:
        _, shapes = get_fno(fno_id)
        for name, _, _ in shapes:
            yield ("fno", fno_id, name)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="architecture id (or fno id)")
    ap.add_argument("--shape", help="shape name")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="run every supported cell")
    ap.add_argument("--list", action="store_true")
    ap.add_argument(
        "--seq-shard", action=argparse.BooleanOptionalAction, default=True,
        help="Megatron-SP activation sharding (default on; --no-seq-shard "
        "lowers the seq-replicated baseline for §Perf comparisons)",
    )
    ap.add_argument("--out-dir", default="artifacts/dryrun")
    args = ap.parse_args()

    # 512 simulated CPU devices; read when the backend first initializes,
    # so this must run before anything asks jax for its devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    jax.config.update("jax_platforms", "cpu")

    if args.list:
        for kind, arch, shape in iter_cells():
            print(f"{kind:4s} {arch:24s} {shape}")
        return

    cells = []
    if args.all:
        cells = list(iter_cells())
    else:
        kind = "fno" if args.arch in FNO_IDS else "lm"
        if kind == "lm":
            ok, why = cell_supported(get_arch(args.arch), get_shape(args.shape))
            if not ok:
                print(f"SKIP {args.arch} x {args.shape}: {why}")
                return
        cells = [(kind, args.arch, args.shape)]

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    failures = []
    for kind, arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} [{'2x16x16' if mp else '16x16'}]"
            try:
                art = run_cell(
                    kind, arch, shape, multi_pod=mp, out_dir=args.out_dir,
                    seq_shard=args.seq_shard,
                )
                print(
                    f"OK  {tag:60s} compile={art['compile_s']:7.1f}s "
                    f"flops={art['hlo_flops']:.3e} coll={art['collectives']['total_bytes']:.3e}B "
                    f"peak={art['memory']['peak_per_device']/2**30:.2f}GiB"
                )
            except Exception as e:  # noqa: BLE001 — report and continue the sweep
                failures.append((tag, repr(e)))
                print(f"FAIL {tag}: {e!r}")
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {[t for t, _ in failures]}")


if __name__ == "__main__":
    main()
