"""Quickstart: the paper's model-parallel FNO in 60 lines.

Runs on CPU with 8 simulated devices: builds a small 4-D FNO, checks that
the domain-decomposed forward (paper Alg. 1/2) matches the serial oracle to
float precision, compares against the paper's pipeline-parallel baseline,
and trains a few steps with the distributed step.

    PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    FNOConfig, fno_forward, forward_and_specs, init_params,
    make_pipeline_forward, mse_loss,
)
from repro.core.partition import make_mesh
from repro.train import AdamWConfig, adamw_update, init_opt_state

cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=8,
                in_channels=1, out_channels=1, n_blocks=4, decoder_dim=16)
key = jax.random.PRNGKey(0)
params = init_params(key, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 16, 16, 8, 8))
y = jnp.tanh(jnp.roll(x, 1, axis=2))  # synthetic target

# --- serial oracle vs domain decomposition (2 data x 4 model devices) ----
mesh = make_mesh((2, 4), ("data", "model"))
fwd_dd, _, _ = forward_and_specs(mesh, cfg, dp_axes=("data",), model_axis="model")
out_serial = jax.jit(lambda p, x: fno_forward(p, x, cfg))(params, x)
out_dd = jax.jit(fwd_dd)(params, x)
np.testing.assert_allclose(np.asarray(out_dd), np.asarray(out_serial), rtol=1e-4, atol=1e-5)
print(f"domain-decomposed == serial  (max diff {float(jnp.abs(out_dd - out_serial).max()):.2e})")

# --- BEYOND-PAPER: 2-D pencil decomposition (2 data x 2 mx x 2 my) --------
# Algorithm 2 shards a single spatial dim, capping model parallelism at
# nx/2mx devices. Passing a PAIR of mesh axes as model_axis shards the
# solution along BOTH x and y (two per-axis all-to-alls; spectral weights
# sharded k_y x k_z), lifting the cap to (nx/2mx)*(ny/2my).
mesh_2d = make_mesh((2, 2, 2), ("data", "mx", "my"))
fwd_2d, _, _ = forward_and_specs(mesh_2d, cfg, dp_axes=("data",), model_axis=("mx", "my"))
out_2d = jax.jit(fwd_2d)(params, x)
np.testing.assert_allclose(np.asarray(out_2d), np.asarray(out_serial), rtol=1e-4, atol=1e-5)
print(f"2-D pencil-decomposed == serial (max diff {float(jnp.abs(out_2d - out_serial).max()):.2e})")

# --- BEYOND-PAPER: fused Pallas spectral path + overlapped all-to-alls ----
# use_pallas=True routes every FNO block's spectral core through one Pallas
# kernel that fuses mode truncation + the complex channel mix + zero-pad
# (one HBM pass instead of three materializations; interpret-mode on CPU,
# compiled on TPU), and comm_chunks=2 splits each pencil all-to-all into
# channel chunks so XLA's latency-hiding scheduler can fly chunk i's wires
# under chunk i+1's local FFTs. Both are bit-for-bit drop-ins: same params,
# same numerics gate as above. Serving additionally caches the weights'
# re/im planes once per checkpoint (params_with_planes) instead of
# re-splitting them every block of every rollout step. Shell:
#   python src/repro/launch/train.py --mode fno ... --use-pallas --comm-chunks 2
#   python src/repro/launch/serve_pde.py --ckpt-dir ... --use-pallas --verify
import dataclasses

fused_cfg = dataclasses.replace(cfg, use_pallas=True, comm_chunks=2)
fwd_fused, _, _ = forward_and_specs(mesh, fused_cfg, dp_axes=("data",), model_axis="model")
out_fused = jax.jit(fwd_fused)(params, x)
np.testing.assert_allclose(np.asarray(out_fused), np.asarray(out_serial), rtol=1e-4, atol=1e-5)
print(f"fused Pallas spectral path == serial (max diff "
      f"{float(jnp.abs(out_fused - out_serial).max()):.2e})")

# --- the paper's pipeline-parallel comparison baseline --------------------
mesh_pp = make_mesh((1, 4), ("data", "model"))
fwd_pp = make_pipeline_forward(mesh_pp, cfg, n_micro=2)
out_pp = jax.jit(fwd_pp)(params, x)
np.testing.assert_allclose(np.asarray(out_pp), np.asarray(out_serial), rtol=1e-4, atol=1e-5)
print("pipeline baseline matches too (but see Fig. 6: its bubble efficiency "
      "is M/(M+P-1) = 0.4 here vs ~1.0 for domain decomposition)")

# --- train a few steps with the distributed forward -----------------------
opt_cfg = AdamWConfig(lr=2e-2)
opt = init_opt_state(params)

@jax.jit
def train_step(params, opt, x, y):
    def loss_fn(p):
        return mse_loss(fwd_dd(p, x), y)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    params, opt, _ = adamw_update(grads, opt, params, opt_cfg)
    return params, opt, loss

losses = []
for step in range(40):
    params, opt, loss = train_step(params, opt, x, y)
    losses.append(float(loss))
    if step % 10 == 0 or step == 39:
        print(f"step {step:3d}  loss {losses[-1]:.5f}")
assert losses[-1] < losses[0], "loss should decrease"

# --- END TO END: cloud datagen -> chunked store -> sharded training -------
# The paper's full pipeline: simulate training pairs in parallel through the
# batch pool, write them spatially chunked (x * y) into the array store with
# streaming normalization stats, then train with every device reading ONLY
# the chunks under its (mx, my) pencil — assembled into globally-sharded
# batches by the ShardedDatasetLoader and consumed via shard_train_step.
# The same thing, from a shell:
#   python -m repro.launch.datagen --pde two_phase --n 8 \
#       --grid 16 8 8 --nt 4 --out /tmp/ds
#   python src/repro/launch/train.py --mode fno --x-store /tmp/ds/x \
#       --y-store /tmp/ds/y --devices 8 --model-shards 2 2
import tempfile

from repro.data import ArrayStore, ShardedDatasetLoader
from repro.launch.datagen import main as datagen
from repro.train import init_opt_state as init_opt, make_train_step
from repro.train.train_loop import shard_train_step

with tempfile.TemporaryDirectory() as tmp:
    datagen([
        "--pde", "two_phase", "--n", "8", "--grid", "16", "8", "8",
        "--nt", "4", "--out", f"{tmp}/ds", "--backend", "thread",
    ])
    xs, ys = ArrayStore.open(f"{tmp}/ds/x"), ArrayStore.open(f"{tmp}/ds/y")
    print(f"stats from meta.json: x mean {xs.meta['stats']['mean'][0]:.4f} "
          f"std {xs.meta['stats']['std'][0]:.4f}")

    e2e_cfg = FNOConfig(grid=(16, 8, 8, 4), modes=(4, 2, 2, 2), width=8,
                        n_blocks=2, decoder_dim=16)
    fwd, spec, p_specs = forward_and_specs(mesh_2d, e2e_cfg, dp_axes=("data",),
                                           model_axis=("mx", "my"))
    jit_step = shard_train_step(
        make_train_step(
            lambda p, b: (mse_loss(fwd(p, b["x"]), b["y"]), {}),
            AdamWConfig(lr=3e-3),
        ),
        mesh_2d,
        p_specs,
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), e2e_cfg)),
        {"x": spec, "y": spec},
    )
    p2 = init_params(jax.random.PRNGKey(0), e2e_cfg)
    o2 = init_opt(p2)
    with ShardedDatasetLoader(
        {"x": xs, "y": ys}, mesh_2d, 2, {"x": spec, "y": spec},
        normalize=("x",),
    ) as loader:
        e2e_losses = []
        for step in range(10):
            p2, o2, m = jit_step(p2, o2, loader.batch(step))
            e2e_losses.append(float(m["loss"]))
    print(f"end-to-end sharded training: loss {e2e_losses[0]:.3e} -> "
          f"{e2e_losses[-1]:.3e} (each device read only its pencil's chunks)")
    assert e2e_losses[-1] < e2e_losses[0]

# --- SERVE THE TRAINED SURROGATE: continuous scenario batching ------------
# The paper's payoff is inference: the surrogate replaces the numerical
# simulator for 1000s-of-scenario workloads (well placement, UQ). Serving
# goes through the SAME slot scheduler that serves LLM tokens — one batched
# model-parallel FNO application per tick, continuous admission, padded
# buckets — with the store's normalization applied on ingress and inverted
# on egress, so outputs are physical saturations.
from repro.data.loader import Normalizer
from repro.data.pde.two_phase import TwoPhaseConfig, random_well_mask
from repro.serve import FNORunner, ScenarioRequest, Scheduler

runner = FNORunner(
    e2e_cfg, p2, mesh=mesh_2d, model_axis=("mx", "my"), max_slots=4,
    x_normalizer=Normalizer.from_source(xs),
)
runner.warmup()
sim_cfg = TwoPhaseConfig(grid=e2e_cfg.grid[:3], nt_frames=e2e_cfg.grid[3])
sched = Scheduler(runner, 4)
for i in range(8):  # a small UQ ensemble of well placements
    mask = random_well_mask(sim_cfg, 2, i)
    x = np.repeat(mask[None, :, :, :, None], e2e_cfg.grid[3], -1)
    sched.submit(ScenarioRequest(rid=i, x=x.astype(np.float32), steps=2))
import time as _time

t0 = _time.perf_counter()
served = sched.run_until_done()
dt = _time.perf_counter() - t0
print(f"served {len(served)} scenarios x 2 rollout steps in {dt:.3f}s "
      f"({len(served)/dt:.1f} scen/s) over {sched.steps} engine ticks, "
      f"model-parallel on {dict(mesh_2d.shape)}")
assert all(len(r.outputs) == 2 for r in served)
# From a shell, the same thing runs off a train.py checkpoint directory
# (train.py persists fno_config.json — architecture + normalization
# snapshot — next to its checkpoints):
#   python src/repro/launch/serve_pde.py --ckpt-dir /tmp/ckpt \
#       --scenarios 64 --max-batch 8 --devices 8 --model-shards 2 2 \
#       --verify --bench-sequential --reference

# --- UQ ENSEMBLE + GEOMODEL CACHE: the KV-cache of PDE serving ------------
# Real UQ ensembles share ONE permeability geomodel across thousands of
# scenarios — only the wells move. Declaring the leading input channels
# static (n_static) makes the runner cache their normalized form and
# encoder prelift by content hash: computed once, replayed for every
# request AND rollout step (the forward lifts only the dynamic channels
# and adds the cached partial sum — bit-identical to recomputing). The
# scheduler additionally dedups byte-identical in-flight scenarios: a
# duplicate never occupies a slot, it receives the primary's outputs.
from repro.launch.datagen import geomodel_channel

uq_cfg = FNOConfig(grid=(16, 8, 8, 4), modes=(4, 2, 2, 2), width=8,
                   n_blocks=2, decoder_dim=16, in_channels=2)
uq_runner = FNORunner(
    uq_cfg, init_params(jax.random.PRNGKey(2), uq_cfg), mesh=mesh_2d,
    model_axis=("mx", "my"), max_slots=4, n_static=1,
)
uq_runner.warmup()
geo = geomodel_channel(uq_cfg.grid[:3], uq_cfg.grid[3])  # shared geomodel
sched = Scheduler(uq_runner, 4)
for i in range(8):
    mask = random_well_mask(sim_cfg, 2, 100 + i)
    well = np.repeat(mask[None, :, :, :, None], uq_cfg.grid[3], -1)
    x = np.concatenate([geo, well.astype(np.float32)], axis=0)
    sched.submit(ScenarioRequest(rid=i, x=x, steps=2))
    sched.submit(ScenarioRequest(rid=100 + i, x=x.copy(), steps=2))  # dup
served = sched.run_until_done()
cache_stats = uq_runner.cache.stats
print(f"UQ ensemble: {len(served)} scenarios served, geomodel-cache "
      f"hit-rate {cache_stats['hit_rate']:.2f} ({cache_stats['hits']} hits /"
      f" {cache_stats['misses']} misses), dedup absorbed "
      f"{sched.dedup_attached} duplicate(s)")
assert cache_stats["hit_rate"] > 0 and sched.dedup_attached == 8
# Shell version — datagen --geomodel writes the log-permeability field as
# a static input channel, so the trained checkpoint serves in ensemble
# mode (vary wells only, report hit-rate; benchmarks/run.py cache measures
# the cold-vs-warm throughput gain):
#   python -m repro.launch.datagen --pde two_phase --geomodel --n 8 \
#       --grid 16 8 8 --nt 4 --out /tmp/geo_ds
#   python src/repro/launch/train.py --mode fno --x-store /tmp/geo_ds/x \
#       --y-store /tmp/geo_ds/y --ckpt-dir /tmp/geo_ckpt
#   python src/repro/launch/serve_pde.py --ckpt-dir /tmp/geo_ckpt \
#       --ensemble --static-channels 1 --dup 2 --verify

# --- FLEET SERVING: a gateway over N replicas -----------------------------
# Production scenario traffic outgrows one scheduler: the Gateway fronts N
# independent replicas (each its own runner + scheduler — in production
# its own host / mesh slice) and ROUTES requests: "affinity" keeps every
# scenario sharing a geomodel on the replica that already cached it, so
# the fleet-wide hit-rate matches the single-process rate and duplicates
# still dedup; a replica whose runner raises is drained and its requests
# fail over to the healthy ones. With one replica the gateway is a
# pass-through — outputs stay bit-identical to the plain scheduler. Two
# geomodel realizations below -> each pins to its own replica, so every
# replica's cache serves exactly one geomodel (the fleet hit-rate match).
from repro.serve import Gateway

fleet = [uq_runner]
for _ in range(1):  # replicate the SAME checkpoint (heterogeneous is fine)
    extra = FNORunner(
        uq_cfg, init_params(jax.random.PRNGKey(2), uq_cfg), mesh=mesh_2d,
        model_axis=("mx", "my"), max_slots=4, n_static=1,
    )
    extra.warmup()
    fleet.append(extra)
gateway = Gateway(fleet, policy="affinity")
geo2 = geomodel_channel(uq_cfg.grid[:3], uq_cfg.grid[3], seed=1)
for i in range(8):
    mask = random_well_mask(sim_cfg, 2, 200 + i)
    well = np.repeat(mask[None, :, :, :, None], uq_cfg.grid[3], -1)
    x = np.concatenate([(geo, geo2)[i % 2], well.astype(np.float32)], axis=0)
    gateway.submit(ScenarioRequest(rid=200 + i, x=x, steps=2))
served = gateway.run_until_done()
stats = gateway.stats()
for rs in stats["replicas"]:
    print(f"  replica {rs['name']}: routed {rs['routed']}, served "
          f"{rs['finished']}, backlog {rs['pending']}")
print(f"fleet: {len(served)} served across {stats['fleet']['n_replicas']} "
      f"replicas, cache hit-rate {stats['fleet']['cache_hit_rate']:.2f}")
assert len(served) == 8 and not gateway.failed
# Shell version (2 replicas restored from one checkpoint; benchmarks/run.py
# gateway measures fleet scenarios/s + p95 vs single-replica under
# open-loop arrivals):
#   python src/repro/launch/serve_pde.py --ckpt-dir /tmp/geo_ckpt \
#       --replicas 2 --policy affinity --ensemble --static-channels 1 \
#       --dup 2 --verify

# --- DEEP CACHE + FLEET-SHARED STORE --------------------------------------
# The geomodel cache goes two levels past the encoder prelift: with
# cache_level="deep" (the default) the runner also caches the first
# spectral block's STATIC kept-mode spectra and weight-mixed contribution.
# FFT -> truncate -> mix is linear, so block 0 runs only on the dynamic
# remainder and the cached contribution is summed straight into its
# pre-activation (core.fno.fno_forward(..., contrib=...)) — bit-identical to
# recomputing, but the whole static spectral prefix is off the per-tick
# path. A fleet-shared CacheStore adds the disaggregated tier behind the
# per-replica LRUs: replicas consult it on local miss and publish fresh
# entries, so a geomodel warmed anywhere is warm fleet-wide — including on
# the replica that inherits an ensemble after a failover re-route.
from repro.serve import DictCacheStore

store = DictCacheStore()  # FileCacheStore(path) for cross-process fleets
deep_fleet = []
for _ in range(2):
    rep = FNORunner(
        uq_cfg, init_params(jax.random.PRNGKey(2), uq_cfg), mesh=mesh_2d,
        model_axis=("mx", "my"), max_slots=4, n_static=1,
        cache_level="deep", cache_store=store,
    )
    rep.warmup()
    deep_fleet.append(rep)
gw2 = Gateway(deep_fleet, policy="affinity")
for i in range(6):
    mask = random_well_mask(sim_cfg, 2, 300 + i)
    well = np.repeat(mask[None, :, :, :, None], uq_cfg.grid[3], -1)
    x = np.concatenate([geo, well.astype(np.float32)], axis=0)
    gw2.submit(ScenarioRequest(rid=300 + i, x=x, steps=2))
served = gw2.run_until_done()
pinned = max(gw2.replicas, key=lambda h: h.routed)  # affinity pins the geo
lv = pinned.runner.cache.stats["level_bytes"]
print(f"deep cache on {pinned.name}: level bytes "
      + ", ".join(f"{k}={v}" for k, v in lv.items())
      + f"; shared store {store.stats['puts']} put(s), "
      f"{store.stats['entries']} entr(y/ies)")
assert len(served) == 6 and not gw2.failed
assert lv["spectra"] > 0 and lv["contribution"] > 0  # the deep levels
assert store.stats["puts"] >= 1  # published for the rest of the fleet
# Shell version (per-level warm-vs-cold speedup + a simulated replica
# failover with a cross-replica store hit live in benchmarks/run.py cache;
# results persist to BENCH_cache.json):
#   python src/repro/launch/serve_pde.py --ckpt-dir /tmp/geo_ckpt \
#       --ensemble --static-channels 1 --cache-level deep \
#       --cache-store /tmp/fleet_store --replicas 2 --verify

# --- ONLINE TRAINING: train while the simulator is still writing ----------
# The paper's biggest adoption cost is that the dataset "must be simulated
# in advance". The streaming path removes it (Meyer-et-al online learning):
# ONE command spawns datagen in the background and starts stepping as soon
# as the first batch's samples are published, drawing every batch from the
# store's complete-prefix watermark. The per-step watermarks are recorded
# to <ckpt-dir>/watermarks.json, so after a crash + restore — or replayed
# against the finished store — the sample schedule is bit-identical, and
# back-pressure (with a stall counter in the final report) kicks in if
# training outpaces simulation:
#
#   python src/repro/launch/train.py --mode fno --online --out /tmp/ds \
#       --pde two_phase --n-data 16 --grid 16 8 8 4 \
#       --devices 8 --model-shards 2 2
#
# The run prints "online: first step with K/N samples complete ...
# overlap=True" — training began while simulation was in flight. Compare
# time-to-first-step against simulate-then-train with:
#   PYTHONPATH=src:. python benchmarks/run.py streaming
print("quickstart OK")
