"""PDE-scenario ModelRunner: model-parallel FNO surrogate inference.

The paper's headline result is inference — the trained surrogate simulates
3-D CO2 flow ~5 orders of magnitude faster than the numerical simulator,
which is what makes 1000s-of-scenarios workloads (well-placement
optimization, uncertainty quantification) tractable. This runner serves
that surrogate through the same slot scheduler that serves LLM tokens:

  * one scheduler tick = one batched FNO application over every active
    slot, jit-compiled once per PADDED BUCKET size (active slots are padded
    up to the next bucket so continuous admission doesn't retrigger
    compilation — and, because XLA results are a function of the batch
    SHAPE, a request's output is bit-identical however admission order or
    slot reuse interleaves it with other traffic of the same bucket);
  * the forward is the family's distributed one when the mesh carries model
    axes (paper Alg. 2 / 2-D pencils) — params and batch go through the
    same ``forward_and_specs`` layout contract the training driver uses,
    so a checkpoint trained model-parallel serves model-parallel;
  * ingress applies the store's persisted per-channel normalization (the
    exact stats training normalized with, snapshotted into the
    checkpoint's ``fno_config.json``); egress inverts the target
    normalization, so callers always see physical units;
  * a request may ask for a multi-step autoregressive rollout: the
    de-normalized prediction is fed back through ``feedback`` to build the
    next input (default: repeat the final predicted saturation frame along
    t), re-encoded, and the slot stays busy for the next tick — long-
    horizon forecasts beyond the training window;
  * with ``n_static > 0`` the first ``n_static`` input channels are STATIC
    (the geomodel: permeability/porosity realizations). UQ ensembles reuse
    the same geomodel across thousands of scenarios, so its normalized form
    and encoder prelift are cached by content hash in a shared
    ``GeomodelCache`` (the KV-cache of PDE serving) and the per-tick
    forward only lifts the dynamic channels (``fno_forward_split``);
    ``feedback`` then produces only the DYNAMIC channels — the geomodel
    persists across rollout steps without re-normalize/re-lift. The runner
    also keys requests by content (``request_key``) so the scheduler can
    dedup identical in-flight scenarios;
  * with a cache, the runner also holds the static rows the forward reads
    (the prelift, and the contribution on the deep level) on the device,
    in a table of its own keyed like the cache: a tick uploads only the
    dynamic channels, and the bucket's static inputs are stacked on the
    device from the table's rows. The rows are the cache entry's arrays,
    uploaded unchanged, and the tick runs the program compiled for a host
    batch, so the outputs are those of host staging, bit for bit.
"""
from __future__ import annotations

import dataclasses
import json
import os
from collections import OrderedDict
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import tracing
from repro.core.fno import (
    FNOConfig, deep_split_forward_and_specs, forward_and_specs, init_params,
    params_with_planes, split_forward_and_specs,
)
from repro.data.loader import Normalizer
from repro.launch.mesh import build_fno_mesh
from repro.serve.cache_store import CacheStore
from repro.serve.geomodel_cache import GeomodelCache, GeomodelEntry, content_key
from repro.train import checkpoint as ckpt_lib

FNO_CONFIG_FILE = "fno_config.json"


@dataclasses.dataclass
class ScenarioRequest:
    """One PDE scenario: an input field -> ``steps`` surrogate applications.

    ``x`` is the RAW (physical-units) input ``[c_in, nx, ny, nz, nt]`` —
    e.g. the binary injector map repeated along t. ``outputs`` collects one
    de-normalized prediction ``[c_out, nx, ny, nz, nt]`` per rollout step.

    ``priority`` / ``deadline_s`` feed the scheduler's admission policy
    (higher priority first; within a priority, earliest deadline — relative
    seconds from submission — first; default: FIFO).
    """

    rid: int
    x: np.ndarray
    steps: int = 1
    outputs: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[Exception] = None
    priority: int = 0
    deadline_s: Optional[float] = None

    @property
    def prediction(self) -> np.ndarray:
        """Final rollout step's de-normalized prediction."""
        if not self.outputs:
            if self.error is not None:
                raise RuntimeError(
                    f"request {self.rid} failed before any rollout step "
                    f"completed: {self.error}"
                ) from self.error
            raise RuntimeError(
                f"request {self.rid} has no completed rollout steps yet — "
                f"it was not served (still queued, or run_until_done ran "
                f"out of max_steps; check Scheduler.finished/.failed)"
            )
        return self.outputs[-1]


def default_feedback(
    y: np.ndarray, cfg: FNOConfig, n_channels: Optional[int] = None
) -> np.ndarray:
    """Next rollout input from a raw prediction: hold the final predicted
    frame and repeat it along t (the saturation state the next window
    evolves from), tiling/truncating channels to ``n_channels`` (default:
    ``in_channels``; runners with static geomodel channels pass the DYNAMIC
    channel count, since the geomodel persists across rollout steps)."""
    want = cfg.in_channels if n_channels is None else n_channels
    nt = cfg.grid[3]
    nxt = np.repeat(y[..., -1:], nt, axis=-1)
    if nxt.shape[0] != want:
        reps = -(-want // nxt.shape[0])
        nxt = np.concatenate([nxt] * reps, axis=0)[:want]
    return np.ascontiguousarray(nxt, np.float32)


def _slice_normalizer(norm: Normalizer, sl: slice) -> Normalizer:
    """Per-channel stats restricted to a channel slice (identity passes
    through: its scalar mean/scale broadcast over any channel count)."""
    if norm.identity or norm.mean.ndim == 0:
        return norm
    return Normalizer(norm.mean[:, sl], norm.scale[:, sl])


def _stack_rows(bucket: int, *inputs) -> tuple:
    """Each input's rows stacked along a new batch axis and zero-padded to
    ``bucket`` rows, as a zeroed host batch pads them."""
    return tuple(
        jnp.pad(jnp.stack(rows), [(0, bucket - len(rows))] + [(0, 0)] * rows[0].ndim)
        for rows in inputs
    )


def _bucket_ladder(max_slots: int, n_dp: int) -> tuple:
    """Padded-bucket sizes: multiples of the data-parallel size (the batch
    sharding constraint), doubling up to max_slots — so at most
    log2(max_slots/n_dp)+1 jit compilations ever happen."""
    buckets, b = [], n_dp
    while b < max_slots:
        buckets.append(b)
        b *= 2
    buckets.append(max(n_dp, -(-max_slots // n_dp) * n_dp))
    return tuple(sorted(set(buckets)))


class FNORunner:
    """ModelRunner serving batched (data x model)-parallel FNO inference."""

    def __init__(
        self,
        cfg: FNOConfig,
        params,
        *,
        mesh=None,
        model_axis=None,
        max_slots: int = 4,
        x_normalizer: Optional[Normalizer] = None,
        y_normalizer: Optional[Normalizer] = None,
        feedback: Optional[Callable] = None,
        buckets: Optional[Sequence[int]] = None,
        n_static: int = 0,
        cache="auto",
        cache_bytes: int = 256 << 20,
        cache_level: str = "deep",
        cache_store: Optional[CacheStore] = None,
    ):
        if mesh is None:
            mesh, model_axis, _ = build_fno_mesh(jax.device_count(), (1,))
        if not 0 <= n_static <= cfg.in_channels:
            raise ValueError(
                f"n_static={n_static} must be in [0, in_channels="
                f"{cfg.in_channels}]"
            )
        if cache_level not in ("prelift", "deep"):
            raise ValueError(
                f"cache_level must be 'prelift' or 'deep', got {cache_level!r}"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.model_axis = model_axis
        self.n_static = int(n_static)
        # "prelift": cache stops at the encoder prelift (PR-6 behavior);
        # "deep": also cache the first block's static kept-mode spectra and
        # weight-mixed contribution, serving through the deep-split forward.
        self._cache_level = cache_level
        # Fleet-shared tier consulted on local-cache miss (cache_store):
        # entries a peer replica computed are pulled instead of recomputed.
        self.cache_store = cache_store
        # "auto": own cache when there are static channels; None: disabled
        # (the uncached reference path — same split forward, no reuse); a
        # GeomodelCache instance may be shared across runners/replicas.
        self.cache: Optional[GeomodelCache] = (
            GeomodelCache(cache_bytes) if (cache == "auto" and n_static) else
            cache if isinstance(cache, GeomodelCache) else None
        )
        # Fused Pallas serving: params are frozen, so the re/im plane
        # layout of w_spec is computed ONCE here (weight-plane cache) and
        # the complex original is dropped — every block of every rollout
        # step reuses the same planes instead of re-splitting.
        self._planes = bool(cfg.use_pallas)
        forward, x_spec, p_specs = forward_and_specs(
            mesh, cfg, dp_axes=("data",), model_axis=model_axis,
            planes=self._planes,
        )
        self._n_dp = mesh.shape["data"]
        self.buckets = (
            tuple(sorted(set(buckets)))
            if buckets
            else _bucket_ladder(max_slots, self._n_dp)
        )
        for b in self.buckets:
            if b % self._n_dp:
                raise ValueError(
                    f"bucket {b} not divisible by data-parallel size "
                    f"{self._n_dp} (buckets: {self.buckets})"
                )
        if self.buckets[-1] < max_slots:
            # bucket_for would otherwise blow up MID-SERVING, the first
            # time enough slots fill — validate where the %n_dp check lives
            raise ValueError(
                f"largest bucket {self.buckets[-1]} < max_slots {max_slots}:"
                f" every active-set size up to max_slots needs a covering "
                f"bucket (buckets: {self.buckets})"
            )
        self.max_slots = max_slots

        def ns(spec_tree):
            return jax.tree.map(
                lambda s: NamedSharding(mesh, s if isinstance(s, P) else P()),
                spec_tree,
                is_leaf=lambda s: isinstance(s, P),
            )

        self._x_sharding = NamedSharding(mesh, x_spec)
        # host copy of the encoder weights: cache misses compute the static
        # prelift on host (numpy), deterministically — cold and warm paths
        # feed the SAME arrays into the same jitted forward, so cached
        # serving is bit-identical to uncached serving
        self._enc_w = np.asarray(jax.device_get(params["encoder"]["w"]), np.float32)
        self._enc_b = np.asarray(jax.device_get(params["encoder"]["b"]), np.float32)
        # deep level: host copy of block 0's spectral weights (taken from
        # the COMPLEX tree, before any planes conversion) for the per-miss
        # numpy spectral prefix
        self._w0 = None
        if n_static and cache_level == "deep":
            self._w0 = np.asarray(
                jax.device_get(params["blocks"]["w_spec"][0])
            ).astype(np.complex64)
        if self._planes:
            params = params_with_planes(params)
        self.params = jax.device_put(params, ns(p_specs))
        # one jit; XLA specializes per bucket shape on first use
        self._forward = jax.jit(
            forward,
            in_shardings=(ns(p_specs), self._x_sharding),
            out_shardings=self._x_sharding,
        )
        self._forward_split = None
        self._forward_deep = None
        static_specs = ()
        if n_static:
            static_specs = (x_spec,)
            split_fwd, _, _ = split_forward_and_specs(
                mesh, cfg, n_static, dp_axes=("data",), model_axis=model_axis,
                planes=self._planes,
            )
            # pre_static [b, width, ...] and x_dyn [b, c_dyn, ...] share the
            # solution layout (channel dim unsharded)
            self._forward_split = jax.jit(
                split_fwd,
                in_shardings=(ns(p_specs), self._x_sharding, self._x_sharding),
                out_shardings=self._x_sharding,
            )
            if cache_level == "deep":
                deep_fwd, _, c_spec, _ = deep_split_forward_and_specs(
                    mesh, cfg, n_static, dp_axes=("data",),
                    model_axis=model_axis, planes=self._planes,
                )
                static_specs = (c_spec, x_spec)
                self._forward_deep = jax.jit(
                    deep_fwd,
                    in_shardings=(
                        ns(p_specs), NamedSharding(mesh, c_spec),
                        self._x_sharding, self._x_sharding,
                    ),
                    out_shardings=self._x_sharding,
                )
        # device table of static rows (see the module docstring), LRU over
        # at most max_slots geomodel keys: the most one bucket reads. Each
        # row is placed as the activations are along the model axes and
        # replicated over "data"; the jitted stack lays the bucket out
        # over "data" as the forward's in_shardings ask.
        self._resident: "OrderedDict[str, tuple]" = OrderedDict()
        self._compiled: dict = {}  # bucket -> compiled_step
        self.resident_hits = 0
        self.resident_fills = 0
        self._row_shardings = tuple(
            NamedSharding(mesh, P(*spec[1:])) for spec in static_specs
        )
        self._stack = jax.jit(
            _stack_rows, static_argnums=0,
            out_shardings=tuple(NamedSharding(mesh, s) for s in static_specs),
        )
        self.x_normalizer = x_normalizer or Normalizer.from_stats(None)
        self.y_normalizer = y_normalizer or Normalizer.from_stats(None)
        self._x_norm_static = _slice_normalizer(self.x_normalizer, slice(0, n_static))
        self._x_norm_dyn = _slice_normalizer(self.x_normalizer, slice(n_static, None))
        n_dyn = cfg.in_channels - n_static
        self.feedback = feedback or (
            lambda y: default_feedback(y, cfg, n_dyn if n_static else None)
        )
        # per-slot state: the ENCODED current input + remaining rollout
        # steps; with static channels the input splits into a per-slot
        # (key, raw static, dynamic) triple — the prelift itself lives in
        # the cache (or is recomputed per tick when the cache is disabled)
        self._inputs: List[Optional[np.ndarray]] = [None] * max_slots
        self._static_key: List[Optional[str]] = [None] * max_slots
        self._static_raw: List[Optional[np.ndarray]] = [None] * max_slots
        self._dyn: List[Optional[np.ndarray]] = [None] * max_slots
        self._remaining: List[int] = [0] * max_slots
        self.batched_steps = 0  # forward launches (vs scenarios served)

    # -- checkpoint loading --------------------------------------------------
    @classmethod
    def from_checkpoint(
        cls,
        ckpt_dir: str,
        *,
        model_shards: Optional[Sequence[int]] = None,
        n_devices: Optional[int] = None,
        step: Optional[int] = None,
        max_slots: int = 4,
        feedback: Optional[Callable] = None,
        n_static: int = 0,
        cache="auto",
        cache_bytes: int = 256 << 20,
        cache_level: str = "deep",
        cache_store: Optional[CacheStore] = None,
        use_pallas: Optional[bool] = None,
        comm_chunks: Optional[int] = None,
    ) -> "FNORunner":
        """Build a runner from a ``train.py --mode fno`` checkpoint dir.

        Reads the ``fno_config.json`` the trainer persists next to its
        checkpoints (architecture + normalization snapshot), restores the
        latest (or ``step``) params re-sharded onto the SERVING mesh —
        which may use a different device count / model-shard layout than
        training did (elastic restore) — and wires the normalizers so
        ingress/egress are in physical units.

        ``use_pallas`` / ``comm_chunks`` default to what training persisted
        (absent in older checkpoints -> unfused, unchunked); pass a value
        to override — the fused and unfused paths are numerically
        equivalent, so a checkpoint trained either way serves either way.
        """
        cfg_path = os.path.join(ckpt_dir, FNO_CONFIG_FILE)
        try:
            with open(cfg_path) as f:
                saved = json.load(f)
        except FileNotFoundError:
            raise FileNotFoundError(
                f"{cfg_path} not found: serve from a checkpoint directory "
                f"written by train.py --mode fno (which persists the FNO "
                f"architecture + normalization snapshot there)"
            ) from None
        cfg = FNOConfig(
            grid=tuple(saved["grid"]),
            modes=tuple(saved["modes"]),
            width=saved["width"],
            in_channels=saved["in_channels"],
            out_channels=saved["out_channels"],
            n_blocks=saved["n_blocks"],
            decoder_dim=saved["decoder_dim"],
        )
        if saved.get("config"):
            # a run started from a named config: serve that config with the
            # recorded overrides, and refuse if it no longer matches the
            # architecture the checkpoint was trained with
            from repro.configs import fno_with_overrides

            named = fno_with_overrides(saved["config"], saved.get("overrides", {}))
            if dataclasses.replace(named, use_pallas=False, comm_chunks=1) != cfg:
                raise ValueError(
                    f"{cfg_path}: {saved['config']} with overrides "
                    f"{saved.get('overrides')} is {named}, but the checkpoint "
                    f"was trained as {cfg}"
                )
        cfg = dataclasses.replace(
            cfg,
            use_pallas=bool(
                saved.get("use_pallas", False) if use_pallas is None
                else use_pallas
            ),
            comm_chunks=int(
                saved.get("comm_chunks", 1) if comm_chunks is None
                else comm_chunks
            ),
        )
        shards = tuple(model_shards or saved.get("model_shards") or (1,))
        mesh, model_axis, _ = build_fno_mesh(
            n_devices if n_devices is not None else jax.device_count(), shards
        )
        from repro.core.fno import param_specs  # specs on the SERVING mesh

        abstract = jax.eval_shape(
            lambda: {"params": init_params(jax.random.PRNGKey(0), cfg)}
        )
        shardings = {
            "params": jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                param_specs(mesh, model_axis),
                is_leaf=lambda s: isinstance(s, P),
            )
        }
        restored, ck_step, _ = ckpt_lib.restore(
            ckpt_dir, abstract, step=step, shardings=shardings
        )
        kind = saved.get("normalizer", "meanstd")
        ndim = len(cfg.grid) + 2
        normalized = saved.get("normalized", [])
        x_norm = (
            Normalizer.from_stats(saved.get("x_stats"), kind, ndim)
            if "x" in normalized
            else Normalizer.from_stats(None)
        )
        y_norm = (
            Normalizer.from_stats(saved.get("y_stats"), kind, ndim)
            if "y" in normalized
            else Normalizer.from_stats(None)
        )
        runner = cls(
            cfg,
            restored["params"],
            mesh=mesh,
            model_axis=model_axis,
            max_slots=max_slots,
            x_normalizer=x_norm,
            y_normalizer=y_norm,
            feedback=feedback,
            n_static=n_static,
            cache=cache,
            cache_bytes=cache_bytes,
            cache_level=cache_level,
            cache_store=cache_store,
        )
        runner.restored_step = ck_step
        return runner

    # -- ModelRunner protocol ------------------------------------------------
    def _check_shape(self, x_raw: np.ndarray) -> np.ndarray:
        expected = (self.cfg.in_channels,) + tuple(self.cfg.grid)
        if tuple(x_raw.shape) != expected:
            raise ValueError(
                f"scenario input shape {tuple(x_raw.shape)} != model's "
                f"{expected}"
            )
        return np.asarray(x_raw, np.float32)

    def _encode(self, x_raw: np.ndarray) -> np.ndarray:
        return self.x_normalizer.encode(self._check_shape(x_raw)[None])[0]

    @property
    def cache_version(self) -> str:
        """Checkpoint+config signature namespacing fleet-shared store
        entries: every weight/stat an entry's arrays depend on is part of
        the digest, so replicas serving different checkpoints (or different
        modes/width/level) can never exchange intermediates."""
        if getattr(self, "_cache_version", None) is None:
            import hashlib

            h = hashlib.blake2b(digest_size=16)
            h.update(repr((
                tuple(self.cfg.grid), tuple(self.cfg.modes), self.cfg.width,
                self.cfg.in_channels, self.n_static, self._cache_level,
            )).encode())
            parts = [self._enc_w, self._enc_b]
            norm = self._x_norm_static
            if not norm.identity:
                parts += [norm.mean, norm.scale]
            if self._w0 is not None:
                parts.append(self._w0)
            for a in parts:
                arr = np.ascontiguousarray(np.asarray(a))
                h.update(str(arr.dtype).encode())
                h.update(str(arr.shape).encode())
                h.update(arr)
            self._cache_version = h.hexdigest()
        return self._cache_version

    @staticmethod
    def _np_gelu(x: np.ndarray) -> np.ndarray:
        """jax.nn.gelu's default tanh approximation, in float32 numpy."""
        x = x.astype(np.float32)
        inner = np.float32(0.7978845608028654) * (
            x + np.float32(0.044715) * x * x * x
        )
        return np.float32(0.5) * x * (np.float32(1.0) + np.tanh(inner))

    def _np_spectra(self, prelift: np.ndarray) -> np.ndarray:
        """Truncated kept-mode spectrum of the static first hidden state,
        computed on host: S(GELU(prelift + b)) — the numpy mirror of
        ``core.fno.spectral_prelift``'s first half. Deterministic, so the
        cold path recomputing it per tick stays bit-identical to warm."""
        h = self._np_gelu(prelift + self._enc_b[:, None, None, None, None])
        xf = np.fft.rfft(h, axis=-1)
        xf = np.fft.fftn(xf, axes=(1, 2, 3))
        mx, my, mz, mt = self.cfg.modes
        for ax, m in ((1, mx), (2, my), (3, mz)):
            lo = np.take(xf, range(m), axis=ax)
            hi = np.take(xf, range(xf.shape[ax] - m, xf.shape[ax]), axis=ax)
            xf = np.concatenate([lo, hi], axis=ax)
        xf = xf[..., :mt]
        return np.ascontiguousarray(xf.astype(np.complex64))

    def _np_contribution(self, spectra: np.ndarray) -> np.ndarray:
        """Block 0's static kept-mode contribution W_0 . S(h_static)."""
        return np.ascontiguousarray(
            np.einsum("ixyzt,ioxyzt->oxyzt", spectra, self._w0)
            .astype(np.complex64)
        )

    def _static_entry(self, key: str, x_static_raw: np.ndarray) -> GeomodelEntry:
        """Geomodel intermediates by content, walked level by level.

        Lookup order: local cache -> fleet-shared store (on local miss) ->
        host recompute of whatever levels are missing (each level derives
        from the previous, so a deep-evicted entry re-pays only the
        spectral prefix, not the normalization). Fresh or deepened entries
        are re-published to both tiers. Cache hit with all levels: the
        stored arrays, untouched — and the miss path is deterministic
        numpy, so cold == warm bitwise.
        """
        deep = self._cache_level == "deep"
        entry = None
        from_store = False
        if self.cache is not None:
            entry = self.cache.get(key)
        if entry is None and self.cache_store is not None:
            entry = self.cache_store.get(self.cache_version, key)
            from_store = entry is not None
        fresh = entry is None
        if fresh:
            normalized = self._x_norm_static.encode(
                np.asarray(x_static_raw, np.float32)[None]
            )[0]
            prelift = np.einsum(
                "ixyzt,io->oxyzt", normalized, self._enc_w[: self.n_static]
            ).astype(np.float32)
            entry = GeomodelEntry(key, normalized, prelift)
        grew = False
        if deep and entry.contribution is None:
            if entry.spectra is None:
                entry = dataclasses.replace(
                    entry, spectra=self._np_spectra(entry.prelift)
                )
            entry = dataclasses.replace(
                entry, contribution=self._np_contribution(entry.spectra)
            )
            grew = True
        if self.cache is not None and (fresh or grew or from_store):
            self.cache.put(key, entry)
        if self.cache_store is not None and (fresh or grew):
            self.cache_store.put(self.cache_version, key, entry)
        return entry

    def request_key(self, req: ScenarioRequest):
        """Content key for scheduler dedup: identical input + identical
        rollout length means byte-identical work (XLA outputs are a
        function of batch shape, not co-batched content)."""
        return (content_key(np.asarray(req.x, np.float32)), int(req.steps))

    def fanout(self, primary: ScenarioRequest, follower: ScenarioRequest) -> None:
        """Give a deduped follower the primary's outputs (shared arrays —
        served outputs are treated as read-only)."""
        follower.outputs = list(primary.outputs)

    def affinity_key(self, req: ScenarioRequest) -> Optional[str]:
        """Fleet cache-affinity key: the content hash of the GEOMODEL only
        (the static channels), not the whole scenario. A gateway routing
        equal keys to the same replica makes that replica's private
        ``GeomodelCache`` hit exactly as a single process would — and keeps
        byte-identical duplicates on one scheduler so in-flight dedup still
        fires. None (no static channels, or an input admit would reject
        anyway) opts the request out of affinity routing."""
        if not self.n_static:
            return None
        x = np.asarray(req.x, np.float32)
        if x.ndim != len(self.cfg.grid) + 1 or x.shape[0] < self.n_static:
            return None
        return content_key(np.ascontiguousarray(x[: self.n_static]))

    def reset(self, req: ScenarioRequest) -> None:
        """Failover resubmission hook: a request pulled off a broken
        replica mid-rollout restarts from its original ``x``, so partial
        outputs are forgotten."""
        req.outputs = []
        req.done = False
        req.error = None

    def admit(self, slot: int, req: ScenarioRequest) -> None:
        if req.steps < 1:
            raise ValueError(f"request {req.rid}: steps must be >= 1")
        if self.n_static:
            x = self._check_shape(req.x)
            static_raw = np.ascontiguousarray(x[: self.n_static])
            # hash once per request; ticks look the entry up by key (the
            # first tick populates the cache on a miss)
            self._static_key[slot] = content_key(static_raw)
            self._static_raw[slot] = static_raw
            self._dyn[slot] = self._x_norm_dyn.encode(x[self.n_static:][None])[0]
        else:
            self._inputs[slot] = self._encode(req.x)
        self._remaining[slot] = int(req.steps)

    def _served_forward(self, bucket: int, static: bool = True):
        """(jitted forward, zero host batch args after params) for one
        bucket: the deep split, the split, or the plain forward — whichever
        ``step`` runs for this runner. ``static=False`` leaves out the
        static args, which the device table then supplies, and gives the
        program compiled for the host batch (``compiled_step``): traced on
        device arrays, whose types carry their sharding, the jitted forward
        would compile a different program, with different rounding."""
        grid = tuple(self.cfg.grid)
        if not self.n_static:
            return self._forward, (
                np.zeros((bucket, self.cfg.in_channels) + grid, np.float32),
            )
        xd = np.zeros(
            (bucket, self.cfg.in_channels - self.n_static) + grid, np.float32
        )
        if not static:
            return self.compiled_step(bucket), (xd,)
        pre = np.zeros((bucket, self.cfg.width) + grid, np.float32)
        if self._forward_deep is None:
            return self._forward_split, (pre, xd)
        ck = np.zeros((bucket, self.cfg.width) + self.cfg.mode_shape, np.complex64)
        return self._forward_deep, (ck, pre, xd)

    @property
    def _holds_rows(self) -> bool:
        """Whether ticks read the static rows from the device table: with
        static channels and a cache (without one, the uncached reference
        path recomputes and uploads them every tick)."""
        return bool(self.n_static) and self.cache is not None

    def warmup(self) -> float:
        """jit-compile every bucket shape up front (zero batches), and with
        the device table its stack for every active count; returns seconds
        spent, so drivers can report compile time separately from
        steady-state serving throughput."""
        import time as _time

        t0 = _time.perf_counter()
        for b in self.buckets:
            fwd, args = self._served_forward(b)
            if self._holds_rows:
                # the table's path: the host batch's program on static args
                # stacked on the device, from every row count this bucket
                # serves; one stacked bucket and one zero row held at a time
                zero = [jnp.zeros(a.shape[1:], a.dtype, device=sh)
                        for a, sh in zip(args, self._row_shardings)]
                for n in range(1, b):
                    if self.bucket_for(n) == b:
                        jax.block_until_ready(self._stack(b, *((z,) * n for z in zero)))
                fwd = self.compiled_step(b)
                args = self._stack(b, *((z,) * b for z in zero)) + args[-1:]
                del zero
            jax.block_until_ready(fwd(self.params, *args))
        return _time.perf_counter() - t0

    def compiled_step(self, bucket: int):
        """The compiled device program ``step`` runs at ``bucket``, lowered
        from the host batch and kept: its ``as_text()`` and
        ``memory_analysis()`` describe what the device executes (e.g.
        whether a Pallas kernel is in it)."""
        if bucket not in self._compiled:
            fwd, args = self._served_forward(bucket)
            self._compiled[bucket] = fwd.lower(self.params, *args).compile()
        return self._compiled[bucket]

    def bucket_for(self, n_active: int) -> int:
        for b in self.buckets:
            if b >= n_active:
                return b
        raise ValueError(
            f"{n_active} active slots exceed the largest bucket "
            f"{self.buckets[-1]}"
        )

    def _static_rows(self, entry: GeomodelEntry) -> tuple:
        """The entry's rows the forward reads as static inputs, in its
        argument order."""
        if self._forward_deep is None:
            return (entry.prelift,)
        return (entry.contribution, entry.prelift)

    def _resident_statics(self, bucket: int, active: Sequence[int], stage: dict):
        """The bucket's static inputs stacked on the device from the table,
        uploading the rows of keys it lacks; returns them and the bytes
        uploaded. The tick's resident keys are touched first, so a fill's
        LRU eviction never drops a row this tick reads."""
        keys = [self._static_key[i] for i in active]
        for key in keys:
            if key in self._resident:
                self._resident.move_to_end(key)
        rows, hits, fills, filled = [], 0, 0, 0
        for i, key in zip(active, keys):
            # looked up every tick as without the table: the cache's
            # hit-rate and LRU order keep counting reuse per rollout step
            entry = self._static_entry(key, self._static_raw[i])
            if key in self._resident:
                hits += 1
            else:
                host = self._static_rows(entry)
                self._resident[key] = tuple(
                    jax.device_put(a, sh) for a, sh in zip(host, self._row_shardings)
                )
                fills += 1
                filled += sum(a.nbytes for a in host)
                if len(self._resident) > self.max_slots:
                    self._resident.popitem(last=False)
            rows.append(self._resident[key])
        self.resident_hits += hits
        self.resident_fills += fills
        stage.update(resident_hits=hits, resident_fills=fills)
        return self._stack(bucket, *zip(*rows)), filled

    def step(self, slots: Sequence[Optional[ScenarioRequest]], active: Sequence[int]) -> list:
        """One tick: stage the active slots' inputs into the bucket's host
        batch (the static rows, where the device table holds them, stacked
        on the device instead), run the forward, feed the outputs back.
        Each phase is recorded as a span (``common.tracing``)."""
        bucket = self.bucket_for(len(active))
        resident = self._holds_rows
        with tracing.span("fno_runner.stage") as stage:
            # staged per tick = per rollout step: the cache turns the
            # static normalize+prelift into a lookup; without it (cache
            # disabled) each tick recomputes — exactly the pre-cache cost
            forward, batch = self._served_forward(bucket, static=not resident)
            for j, i in enumerate(active):
                if resident:
                    rows = (self._dyn[i],)
                elif self.n_static:
                    entry = self._static_entry(self._static_key[i], self._static_raw[i])
                    rows = self._static_rows(entry) + (self._dyn[i],)
                else:
                    rows = (self._inputs[i],)
                for arr, row in zip(batch, rows):
                    arr[j] = row
            uploaded = sum(arr.nbytes for arr in batch)
            if resident:
                statics, filled = self._resident_statics(bucket, active, stage)
                batch = statics + batch
                uploaded += filled
        with tracing.span("fno_runner.forward", bytes=uploaded):
            yb = np.asarray(forward(self.params, *batch))
        self.batched_steps += 1
        with tracing.span("fno_runner.feedback"):
            return self._feed_back(slots, active, yb)

    def _feed_back(self, slots, active, yb: np.ndarray) -> list:
        """Append each active slot's de-normalized output to its request
        and re-encode the next rollout input; returns the slots done."""
        finished = []
        grid = tuple(self.cfg.grid)
        n_dyn = self.cfg.in_channels - self.n_static
        for j, i in enumerate(active):
            req = slots[i]
            y_raw = self.y_normalizer.decode(yb[j : j + 1])[0]
            req.outputs.append(y_raw)
            self._remaining[i] -= 1
            if self._remaining[i] > 0:
                fb = np.asarray(self.feedback(y_raw), np.float32)
                if self.n_static:
                    # feedback evolves only the DYNAMIC channels; the
                    # geomodel persists (and stays cached) for the slot
                    if tuple(fb.shape) != (n_dyn,) + grid:
                        raise ValueError(
                            f"feedback returned shape {tuple(fb.shape)}; "
                            f"with n_static={self.n_static} it must return "
                            f"the dynamic channels {(n_dyn,) + grid}"
                        )
                    self._dyn[i] = self._x_norm_dyn.encode(fb[None])[0]
                else:
                    self._inputs[i] = self._encode(fb)
            else:
                finished.append(i)
        return finished

    def retire(self, slot: int, req: ScenarioRequest) -> None:
        self._inputs[slot] = None
        self._static_key[slot] = None
        self._static_raw[slot] = None
        self._dyn[slot] = None
        self._remaining[slot] = 0
