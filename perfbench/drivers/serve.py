"""Serving traffic: a closed loop of clients over ``Scheduler`` +
``FNORunner``, each client sending its next scenario as soon as its last
one completes.

Traffic parameters (``traffic/<name>.json``, kind ``serve``):

* ``clients``: outstanding scenarios; ``slots``: scheduler slots, and the
  runner's only bucket;
* ``rollout_steps``: surrogate applications per scenario;
* ``wells``: injectors per scenario, placed at random; no two scenarios
  of a run share a placement, so in-flight dedup never fires;
* ``geomodel``: ``shared`` (one seeded geomodel for every scenario) or
  ``per_scenario`` (a seeded realization each);
* ``n_static``: static channels the runner caches (0: the plain forward);
  ``cache_bytes``: the geomodel cache's budget;
* ``check_sample``: completed scenarios compared with the reference.

Set-up makes the weights on the device from the seed, builds the runner,
compiles its bucket, and serves one scenario of its own through a
scheduler, which fills the geomodel cache where there is one. The window
opens with the clients' first submissions and closes at the first
completion at or after ``--seconds``, so its rate counts whole scenarios
over the whole time they took. Latency runs from a client's submission to
the tick after which its last output is on the host.
"""
from __future__ import annotations

import functools
import time

import numpy as np


class Driver:
    def __init__(self, cell, model, devices, seed, spans):
        self.cell, self.model, self.devices = cell, model, devices
        self.seed, self.spans = seed, spans
        self.cfg, self.tr = cell.config, cell.traffic
        self.counts = {}

    # -- traffic -------------------------------------------------------
    def _pool(self):
        """Per-scenario (geomodel seed, well placement), all distinct, in
        the seed's order; index 0 is set-up's own scenario."""
        m, grid3 = self.model, tuple(self.cfg["grid"][:3])
        rng = m.np_rng(self.seed, 1)
        seen, pool = set(), []
        for _ in range(100 * self.tr["pool"]):
            if len(pool) == self.tr["pool"]:
                break
            wells = m.well_positions(grid3, self.tr["wells"], rng)
            if len(set(wells)) < len(wells) or wells in seen:
                continue
            seen.add(wells)
            pool.append((int(rng.integers(2**31)), wells))
        else:
            raise ValueError(f"the grid holds fewer than {self.tr['pool']} "
                             f"distinct placements of {self.tr['wells']} wells")
        return pool

    def _scenario(self, i: int) -> np.ndarray:
        t0 = time.perf_counter()
        geo_seed, wells = self.pool[i]
        if self.shared_logk is None:
            logk = self.model.log_permeability(tuple(self.cfg["grid"][:3]), geo_seed)
        else:
            logk = self.shared_logk
        x = self.model.scenario_input(self.cfg, logk, wells)
        self.gen_s += time.perf_counter() - t0
        return x

    # -- phases --------------------------------------------------------
    def setup(self):
        import jax

        from repro.core.fno import FNOConfig
        from repro.data.loader import Normalizer
        from repro.launch.mesh import build_fno_mesh
        from repro.serve import FNORunner, Scheduler
        from repro.serve.geomodel_cache import GeomodelCache

        cfg, tr, m = self.cfg, self.tr, self.model
        fcfg = FNOConfig(
            grid=tuple(cfg["grid"]), modes=tuple(cfg["modes"]), width=cfg["width"],
            in_channels=cfg["in_channels"], out_channels=cfg["out_channels"],
            n_blocks=cfg["n_blocks"], decoder_dim=cfg["decoder_dim"],
            use_pallas=cfg["serve_use_pallas"],
        )
        mesh, model_axis, _ = build_fno_mesh(len(self.devices), cfg["model_shards"])
        params = jax.jit(functools.partial(m.make_params, cfg=cfg))(m.jax_key(self.seed))
        n_static = tr["n_static"]
        self.runner = FNORunner(
            fcfg, params, mesh=mesh, model_axis=model_axis,
            max_slots=tr["slots"], buckets=(tr["slots"],),
            x_normalizer=Normalizer(*m.stats_arrays(cfg["x_stats"])),
            y_normalizer=Normalizer(*m.stats_arrays(cfg["y_stats"])),
            n_static=n_static,
            cache=GeomodelCache(tr["cache_bytes"]) if n_static else None,
            cache_level="deep",
        )
        del params
        self.Scheduler = Scheduler
        self.pool = self._pool()
        self.gen_s = 0.0
        self.shared_logk = None
        if tr["geomodel"] == "shared":
            self.shared_logk = m.log_permeability(tuple(cfg["grid"][:3]), self.pool[0][0])
        self.runner.warmup()
        sched = Scheduler(self.runner, tr["slots"])
        sched.submit(self._req(0))
        sched.run_until_done(max_steps=10 * tr["rollout_steps"])
        if len(sched.finished) != 1:
            raise RuntimeError(f"set-up's scenario did not serve: {sched.failed}")
        self.next_i = 1

    def _req(self, i):
        from repro.serve import ScenarioRequest

        return ScenarioRequest(rid=i, x=self._scenario(i), steps=self.tr["rollout_steps"])

    def window(self, seconds: float) -> dict:
        tr = self.tr
        sched = self.Scheduler(self.runner, tr["slots"])
        submitted, latencies, done = {}, [], []
        self.gen_s = 0.0

        def submit():
            if self.next_i >= len(self.pool):
                raise RuntimeError(f"the pool of {len(self.pool)} scenarios ran out")
            req = self._req(self.next_i)
            self.next_i += 1
            submitted[req.rid] = time.perf_counter()
            sched.submit(req)

        t0 = time.perf_counter()
        for _ in range(tr["clients"]):
            submit()
        ticks = steps = seen = failed_seen = 0
        while True:
            with self.spans("tick"):
                steps += sched.step()
            ticks += 1
            now = time.perf_counter()
            new = sched.finished[seen:]
            seen = len(sched.finished)
            fresh_fail = len(sched.failed) - failed_seen
            failed_seen = len(sched.failed)
            for r in new:
                latencies.append(now - submitted[r.rid])
                done.append(r)
            for _ in range(len(new) + fresh_fail):
                with self.spans("submit"):
                    submit()
            if (now - t0 >= seconds and new) or now - t0 >= seconds + 300:
                break
        window_s = now - t0
        if sched.dedup_attached:
            raise RuntimeError("in-flight dedup fired: the traffic repeated a scenario")
        self.done, self.latencies, self.window_s = done, latencies, window_s
        self.counts = {
            "attempted": len(done) + failed_seen, "failed": failed_seen,
            "ticks": ticks, "rollout_steps": steps, "scenarios": len(done),
            "window_s": window_s, "bucket": tr["slots"], "generator_s": self.gen_s,
        }
        cache = getattr(self.runner, "cache", None)
        if cache is not None:
            self.counts["cache"] = dict(cache.stats)
        print(f"perfbench: window {window_s:.3f} s, {ticks} ticks, {len(done)} "
              f"scenarios, generator {self.gen_s:.3f} s", flush=True)
        return self.counts

    def end_to_end(self) -> dict:
        lat = np.asarray(self.latencies)
        return {
            "serve_scenarios_per_s": len(self.done) / self.window_s,
            "serve_p95_s": float(np.percentile(lat, 95)) if len(lat) else float("inf"),
        }

    def release(self):
        """Keep the sample the check compares; free the program's state."""
        rng = self.model.np_rng(self.seed, 2)
        k = min(self.tr["check_sample"], len(self.done))
        pick = sorted(rng.choice(len(self.done), size=k, replace=False)) if k else []
        self.sample = [(self.done[i].x, list(self.done[i].outputs)) for i in pick]
        del self.runner, self.done

    # -- the check -----------------------------------------------------
    def reference_rollouts(self, precision: str) -> list:
        """The reference's rollout of every sampled scenario's input."""
        import jax

        cfg, m = self.cfg, self.model
        params = jax.jit(functools.partial(m.make_params, cfg=cfg))(m.jax_key(self.seed))
        fwd = jax.jit(functools.partial(m.forward, cfg=cfg, precision=precision))
        xm, xs = m.stats_arrays(cfg["x_stats"])
        ym, ys = m.stats_arrays(cfg["y_stats"])
        n_static = self.tr["n_static"]
        n_dyn = cfg["in_channels"] - n_static
        outs = []
        for x, _ in self.sample:
            steps = []
            for _ in range(self.tr["rollout_steps"]):
                y = np.asarray(fwd(params, (x[None] - xm) / xs))
                y_raw = (y * ys + ym)[0]
                steps.append(y_raw)
                fb = m.feedback(y_raw, n_dyn)
                x = np.concatenate([x[:n_static], fb]) if n_static else fb
            outs.append(steps)
        return outs

    # each gap, from a served output's difference ``d`` to the reference's ``r``
    GAPS = {
        "out_rel_gap": lambda d, r: np.abs(d).max() / np.abs(r).max(),
        "out_l2_gap": lambda d, r: np.linalg.norm(d.astype(np.float64))
        / np.linalg.norm(r.astype(np.float64)),
    }

    @classmethod
    def step_gaps(cls, got: list, ref: list) -> dict:
        """Each gap at each rollout step, the worst over the sampled
        scenarios: ``out_rel_gap``, the largest |got - ref| over the
        largest |ref|; ``out_l2_gap``, ||got - ref|| over ||ref||."""
        return {name: [max(float(f(g[s] - r[s], r[s])) for g, r in zip(got, ref))
                       for s in range(len(ref[0]))]
                for name, f in cls.GAPS.items()}

    def check(self, got: list | None = None) -> dict:
        """The numbers compared (the gaps the cell's limits name, worst
        over the rollout steps), for the served outputs or for ``got``
        (the control's, in the program's place)."""
        if not self.sample:
            return {"served": {"value": 1.0, "limit": 0.0}}
        if not hasattr(self, "ref"):
            self.ref = self.reference_rollouts("highest")
        if got is None:
            got = [outs for _, outs in self.sample]
        gaps = self.step_gaps(got, self.ref)
        self.detail = {f"{k}_by_step": v for k, v in gaps.items()}
        return {k: {"value": max(v), "limit": self.cell.limits[k]}
                for k, v in gaps.items() if k in self.cell.limits}

    def control_outputs(self) -> list:
        """The reference one precision lower, in the program's place."""
        return self.reference_rollouts("high")
