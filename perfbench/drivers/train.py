"""Training traffic: the program's sharded train step fed by its
``ShardedDatasetLoader``, on samples generated from the seed.

Traffic parameters (``traffic/<name>.json``, kind ``train``):

* ``batch``: global batch; ``samples``: distinct samples in the data set
  (seeded geomodel + well placements, a smooth transform of the wells as
  the target); ``prefetch``: the loader's prefetch depth;
* ``in_flight``: train steps the host may run ahead of the device;
* ``check_steps``: steps the reference follows (the first ones).

Set-up builds ONE object, the jitted step with its state made on the
device from the seed, and drives it through the first ``check_steps``
steps on distinct rows through the window's own call and feed (which
compiles it), keeping what the check compares: each step's loss, the
per-leaf norm of the first gradient as AdamW holds it after one step
(its first moment over 1 - b1), and the per-leaf norm of the parameters'
change over those steps. The window continues the same object; it ends
on ``block_until_ready``.
"""
from __future__ import annotations

import collections
import functools
import math
import time

import numpy as np


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.abs(x) ** 2)) for x in jax.tree.leaves(tree)]


class Driver:
    def __init__(self, cell, model, devices, seed, spans):
        self.cell, self.model, self.devices = cell, model, devices
        self.seed, self.spans = seed, spans
        self.cfg, self.tr = cell.config, cell.traffic

    def _data(self):
        """x [n, c_in, *grid] and y [n, c_out, *grid]: one seeded geomodel
        realization and a distinct well placement per sample."""
        m, cfg = self.model, self.cfg
        grid3 = tuple(cfg["grid"][:3])
        rng = m.np_rng(self.seed, 3)
        xs, seen = [], set()
        while len(xs) < self.tr["samples"]:
            wells = m.well_positions(grid3, 2, rng)
            if len(set(wells)) < 2 or wells in seen:
                continue
            seen.add(wells)
            logk = m.log_permeability(grid3, int(rng.integers(2**31)))
            xs.append(m.scenario_input(cfg, logk, wells))
        x = np.stack(xs)
        y = np.stack([m.training_target(xi, cfg) for xi in x])
        return x, y

    def _opt(self):
        o = self.cfg["optimizer"]
        return {k: o[k] for k in ("lr", "b1", "b2", "eps", "weight_decay", "grad_clip")}

    def setup(self):
        import jax

        from repro.core.fno import FNOConfig, forward_and_specs, mse_loss
        from repro.data import NdArraySource, ShardedDatasetLoader
        from repro.launch.mesh import build_fno_mesh
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.train.train_loop import (
            make_train_step, shard_train_step, train_state_shardings,
        )

        cfg, tr, m = self.cfg, self.tr, self.model
        fcfg = FNOConfig(
            grid=tuple(cfg["grid"]), modes=tuple(cfg["modes"]), width=cfg["width"],
            in_channels=cfg["in_channels"], out_channels=cfg["out_channels"],
            n_blocks=cfg["n_blocks"], decoder_dim=cfg["decoder_dim"],
            use_pallas=cfg["train_use_pallas"],
        )
        mesh, model_axis, _ = build_fno_mesh(len(self.devices), cfg["model_shards"])
        fwd, x_spec, p_specs = forward_and_specs(
            mesh, fcfg, dp_axes=("data",), model_axis=model_axis)

        def loss_fn(params, batch):
            return mse_loss(fwd(params, batch["x"]), batch["y"]), {}

        opt = self._opt()
        self.b1 = opt["b1"]
        step_fn = make_train_step(loss_fn, AdamWConfig(**opt))
        make = functools.partial(m.make_params, cfg=cfg)
        key = m.jax_key(self.seed)
        abstract = jax.eval_shape(make, key)
        specs = {"x": x_spec, "y": x_spec}
        self.step = shard_train_step(step_fn, mesh, p_specs, abstract, specs,
                                     dp_axes=("data",))
        shardings = train_state_shardings(mesh, p_specs, abstract, dp_axes=("data",))
        init = jax.jit(lambda k: (lambda p: {"params": p, "opt": init_opt_state(p)})(make(k)),
                       out_shardings=shardings)
        make_sharded = jax.jit(make, out_shardings=shardings["params"])
        norms = jax.jit(_leaf_norms)
        change = jax.jit(lambda a, b: _leaf_norms(jax.tree.map(lambda u, v: u - v, a, b)))

        self.x, self.y = self._data()
        stats = {"mean": cfg["x_stats"]["mean"], "std": cfg["x_stats"]["std"]}
        self.loader = ShardedDatasetLoader(
            {"x": NdArraySource(self.x, stats), "y": NdArraySource(self.y)},
            mesh, tr["batch"], specs, seed=self.seed % 2**63, shuffle=True,
            normalize=("x",), prefetch=tr["prefetch"],
        )
        state = init(key)
        params, opt_state = state["params"], state["opt"]
        losses, self.ids = [], []
        for s in range(tr["check_steps"]):
            self.ids.append(self.loader.sample_ids(s))
            params, opt_state, metrics = self.step(params, opt_state, self.loader.batch(s))
            losses.append(metrics["loss"])
            if s == 0:
                self.grad_norms = [float(v) / (1 - self.b1) for v in norms(opt_state["mu"])]
        p0 = make_sharded(key)
        self.change_norms = [float(v) for v in change(params, p0)]
        del p0
        self.losses = [float(v) for v in losses]
        rows = np.concatenate(self.ids)
        if len(set(rows.tolist())) != len(rows):
            raise RuntimeError(f"the checked steps repeat a row: {rows}")
        self.state = (params, opt_state)
        self.next_step = tr["check_steps"]

    def window(self, seconds: float) -> dict:
        import jax

        params, opt_state = self.state
        pending = collections.deque()
        n = failed = 0
        t0 = time.perf_counter()
        while True:
            with self.spans("batch"):
                batch = self.loader.batch(self.next_step)
            with self.spans("step"):
                params, opt_state, metrics = self.step(params, opt_state, batch)
            self.next_step += 1
            n += 1
            pending.append(metrics["loss"])
            if len(pending) > self.tr["in_flight"]:
                with self.spans("wait"):
                    failed += not math.isfinite(float(pending.popleft()))
            if time.perf_counter() - t0 >= seconds:
                break
        with self.spans("block"):
            jax.block_until_ready((params, opt_state))
            failed += sum(not math.isfinite(float(v)) for v in pending)
        self.window_s = time.perf_counter() - t0
        self.n_steps = n
        self.state = (params, opt_state)
        self.counts = {"attempted": n, "failed": failed, "steps": n,
                       "window_s": self.window_s, "batch": self.tr["batch"]}
        print(f"perfbench: window {self.window_s:.3f} s, {n} steps", flush=True)
        return self.counts

    def end_to_end(self) -> dict:
        return {"train_step_ms": 1e3 * self.window_s / self.n_steps}

    def release(self):
        self.loader.close()
        del self.state, self.loader, self.step

    # -- the check -----------------------------------------------------
    def reference(self, precision: str) -> dict:
        """The reference's losses, first-gradient leaf norms and change
        leaf norms over the checked steps, on the same rows, on the cell's
        first chip (the one-chip model fits it whole)."""
        import jax

        cfg, m = self.cfg, self.model
        opt = self._opt()
        xm, xs = m.stats_arrays(cfg["x_stats"])
        dev = self.devices[0]
        with jax.default_device(dev):
            p0 = jax.jit(functools.partial(m.make_params, cfg=cfg))(m.jax_key(self.seed))
            grad = jax.jit(jax.value_and_grad(functools.partial(
                m.loss, cfg=cfg, precision=precision)))
            update = jax.jit(functools.partial(m.adamw, opt=opt))
            params, state = p0, m.adamw_init(p0)
            losses = []
            for s, ids in enumerate(self.ids):
                loss, g = grad(params, (self.x[ids] - xm) / xs, self.y[ids])
                losses.append(float(loss))
                params, state = update(params, g, state)
                if s == 0:
                    g_norms = [float(v) / (1 - opt["b1"]) for v in _leaf_norms(state["mu"])]
            change = [float(v) for v in _leaf_norms(
                jax.tree.map(lambda u, v: u - v, params, p0))]
        return {"losses": losses, "grad_norms": g_norms, "change_norms": change}

    @staticmethod
    def gaps(got: dict, ref: dict) -> dict:
        """The numbers a cell may compare (those its limits file names).
        Norm gaps are taken leaf by leaf, against
        the larger of the leaf's reference norm and the median leaf's;
        leaves whose reference gradient is under a thousandth of the median
        leaf's move by round-off alone and are left out of the change."""
        loss = max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"]))
        g_ref = np.asarray(ref["grad_norms"])
        g_med = float(np.median(g_ref))
        grad = max(abs(a - b) / max(b, g_med)
                   for a, b in zip(got["grad_norms"], g_ref))
        moved = g_ref >= 1e-3 * g_med
        c_ref = np.asarray(ref["change_norms"])
        c_med = float(np.median(c_ref[moved]))
        change = max(abs(a - b) / max(b, c_med)
                     for a, b, k in zip(got["change_norms"], c_ref, moved) if k)
        return {"loss_rel_gap": float(loss), "grad_norm_gap": float(grad),
                "change_norm_gap": float(change)}

    def got(self) -> dict:
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}

    def check(self, got: dict | None = None) -> dict:
        """The numbers compared, for the program's readings or for ``got``
        (the control's, in the program's place)."""
        if not hasattr(self, "ref"):
            self.ref = self.reference("highest")
        values = self.gaps(self.got() if got is None else got, self.ref)
        return {k: {"value": v, "limit": self.cell.limits[k]}
                for k, v in values.items() if k in self.cell.limits}

    def control_outputs(self) -> dict:
        """The reference one precision lower, in the program's place."""
        return self.reference("high")
