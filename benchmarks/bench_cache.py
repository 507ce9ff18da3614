"""Geomodel content-hash cache: cold vs warm UQ-ensemble serving, per level.

The paper's UQ workload serves an ensemble where every scenario shares the
SAME geomodel (permeability realization) and only the well placement
varies. The static-channel work repeated per scenario per rollout step is
then identical: normalize + encoder prelift (cache level ``prelift``), and
— one level deeper — the first block's static kept-mode spectra and
weight-mixed contribution (level ``deep``, the block-input split of
``fno_forward(..., contrib=...)``). ``GeomodelCache`` computes each level once and
replays the stored arrays by content hash. This benchmark serves the same
vary-wells-only ensemble cold (cache disabled) vs warm at BOTH levels over
warm (pre-compiled) runners and reports the per-level throughput ratio —
the deep level must beat the encoder-only speedup, since its cold path
re-pays the spectral prefix too.

Correctness is part of the contract: the cold and warm passes must produce
BITWISE-identical outputs (both run the same split forward fed the same
deterministic host-computed arrays; the cache only changes whether they
are recomputed), asserted request-by-request.

A second section exercises the fleet-shared cache store: two replicas
behind an affinity gateway share a ``DictCacheStore``; the ensemble warms
the pinned replica (and the store), the pinned replica is then broken
mid-wave and the failover re-route lands on the other replica — whose
local cache is cold but whose store lookup HITS, so the geomodel stays
warm fleet-wide. Outputs after failover are asserted bitwise-identical to
the cold reference.
"""
from __future__ import annotations

import time

import numpy as np


def _serve_pass(runner, requests, max_slots):
    from repro.serve import Scheduler

    sched = Scheduler(runner, max_slots)
    for r in requests:
        sched.submit(r)
    t0 = time.perf_counter()
    done = sched.run_until_done(max_steps=10000)
    dt = time.perf_counter() - t0
    assert len(done) == len(requests), (len(done), len(requests))
    return done, dt


def _assert_bitwise(ref_done, got_done, label):
    for rc, rw in zip(ref_done, got_done):
        assert rc.rid == rw.rid and len(rc.outputs) == len(rw.outputs)
        for yc, yw in zip(rc.outputs, rw.outputs):
            if not np.array_equal(np.asarray(yc), np.asarray(yw)):
                raise AssertionError(
                    f"{label}: output differs from cold for rid {rc.rid}"
                )


def run(n_scenarios: int = 16, max_slots: int = 4, rollout_steps: int = 4,
        repeats: int = 3):
    import jax

    from repro.core import FNOConfig, init_params
    from repro.core.partition import make_mesh
    from repro.data.loader import Normalizer
    from repro.launch.serve_pde import build_scenarios
    from repro.serve import DictCacheStore, FNORunner, Gateway, GeomodelCache

    # Geomodel-heavy toy: many static channels on a grid large enough that
    # the per-tick static normalize + prelift + spectral prefix is a
    # visible slice of the tick, next to a deliberately small network —
    # the regime the cache targets (real Sleipner-scale geomodels dwarf
    # the per-step dynamics).
    n_static = 48
    cfg = FNOConfig(
        grid=(32, 16, 8, 8), modes=(2, 2, 2, 2), width=4, n_blocks=1,
        decoder_dim=8, in_channels=n_static + 1,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    x_stats = {
        "mean": np.linspace(0.1, 0.7, cfg.in_channels).tolist(),
        "std": [0.5] * cfg.in_channels,
    }
    y_stats = {"absmax": [1.0] * cfg.out_channels}

    def make_runner(level, cache, store=None):
        return FNORunner(
            cfg,
            params,
            mesh=make_mesh((1,), ("data",)),
            model_axis=None,
            max_slots=max_slots,
            x_normalizer=Normalizer.from_stats(x_stats, "meanstd"),
            y_normalizer=Normalizer.from_stats(y_stats, "absmax"),
            n_static=n_static,
            cache=cache,
            cache_level=level,
            cache_store=store,
        )

    def make_requests():
        reqs, _ = build_scenarios(
            cfg, n_scenarios, wells=1, seed=0, steps=rollout_steps,
            n_static=n_static,
        )
        return reqs

    derived = {}
    level_done = {}
    for level in ("prelift", "deep"):
        cache = GeomodelCache()
        runner = make_runner(level, cache)
        runner.warmup()
        # cold: same forward, same host math — just recomputed every tick
        # (this IS the uncached path the cache must match bitwise); at the
        # deep level the cold path re-pays the spectral prefix too.
        runner.cache = None
        cold = [
            _serve_pass(runner, make_requests(), max_slots)
            for _ in range(repeats)
        ]
        cold_dt = min(dt for _, dt in cold)
        cold_done = cold[-1][0]

        runner.cache = cache
        warm = []
        for _ in range(repeats):
            cache.clear()  # warm from empty: first tick misses, rest hit
            warm.append(_serve_pass(runner, make_requests(), max_slots))
        warm_dt = min(dt for _, dt in warm)
        warm_done = warm[-1][0]
        # hit/miss counters accumulate across passes, but every pass
        # repeats the identical lookup pattern, so the ratio IS per-pass
        stats = cache.stats

        _assert_bitwise(cold_done, warm_done, f"warm[{level}]")
        level_done[level] = cold_done
        derived.update({
            f"cold_scen_s_{level}": round(n_scenarios / cold_dt, 2),
            f"warm_scen_s_{level}": round(n_scenarios / warm_dt, 2),
            f"warm_speedup_{level}": round(cold_dt / warm_dt, 2),
        })
        if level == "deep":
            per_scen_us = warm_dt / n_scenarios * 1e6
            derived.update({
                "warm_speedup": round(cold_dt / warm_dt, 2),
                "hit_rate": round(stats["hit_rate"], 3),
                "cache_entries": stats["entries"],
                "cache_mb": round(stats["bytes"] / 1e6, 2),
            })
    derived["deep_beats_prelift"] = int(
        derived["warm_speedup_deep"] > derived["warm_speedup_prelift"]
    )
    derived["bitwise_identical"] = 1

    # -- fleet-shared store across a failover re-route ----------------------
    store = DictCacheStore()
    runners = [make_runner("deep", GeomodelCache(), store) for _ in range(2)]
    for r in runners:
        r.warmup()
    gateway = Gateway(runners, policy="affinity")
    # wave 1: the shared geomodel pins every scenario to one replica,
    # warming its local cache AND publishing the entry to the store
    for req in make_requests():
        gateway.submit(req)
    wave1 = gateway.run_until_done(max_steps=10000)
    assert len(wave1) == n_scenarios
    pinned = max(gateway.replicas, key=lambda r: r.routed)
    other = next(r for r in gateway.replicas if r is not pinned)
    assert other.routed == 0, "affinity should pin the ensemble to one replica"

    # break the pinned replica: its next scheduler step raises, the
    # gateway fails over and re-routes the in-flight wave to the survivor
    def _dead_step(slots, active):
        raise RuntimeError("simulated replica hardware failure")

    pinned.runner.step = _dead_step
    wave2 = make_requests()
    for req in wave2:
        gateway.submit(req)
    gateway.run_until_done(max_steps=10000)
    assert all(req.done and req.error is None for req in wave2)
    # the survivor's LOCAL cache was cold for this geomodel — the store is
    # what kept it warm fleet-wide
    assert store.hits >= 1, store.stats
    assert other.runner.cache.stats["entries"] >= 1
    _assert_bitwise(level_done["deep"], sorted(wave2, key=lambda r: r.rid),
                    "post-failover")
    fleet = gateway.stats()["fleet"]
    derived.update({
        "store_hits_after_failover": store.hits,
        "store_puts": store.puts,
        "fleet_cache_hit_rate": round(fleet["cache_hit_rate"], 3),
        "fleet_rerouted": fleet["rerouted"],
        "failover_bitwise": 1,
    })
    return per_scen_us, derived


if __name__ == "__main__":
    print(run())
