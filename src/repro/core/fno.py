"""Fourier Neural Operator — serial oracle and model-parallel (paper Alg. 1/2).

Functional, pytree-parameterized. One forward body, ``fno_forward``, serves
every mesh layout and every serving prefix; the layout picks the transform
pair its blocks run (``_layout``):

  * no model axis  — the serial pair (``dfft.serial_forward``);
  * one axis name  — paper Alg. 2: X sharded along x, spectral weights
                     along k_y (``dfft.dist_forward``), inside shard_map;
  * an axis pair   — the 2-D pencil: X sharded along x and y, spectral
                     weights along k_y x k_z (``dfft.dist_forward_2d``).

``forward_and_specs`` builds the forward and its layouts for a mesh, so
distributed-vs-serial equivalence is testable to numerical precision.

Architecture (paper Alg. 1): 1x1-conv encoder -> n_blocks x [spectral conv
+ 1x1 bypass, GELU] -> 2-layer decoder. Spectral weights are complex64 and
dominate memory (as in the paper, where the FNO fills 80% of an 80GB A100).

Each layer runs under a ``jax.named_scope``, which a profile shows in the
op path of every device op: ``encoder``; ``blocks`` around the scan over
the blocks, and in each block ``fft_fwd`` (with its all-to-alls), ``mix``,
``fft_inv`` and ``bypass`` (1x1 conv, residual add, GELU); ``decoder``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.common import compat
from repro.core import dfft
from repro.core.dfft import BDIM, CDIM, XDIM, YDIM, ZDIM, TDIM
from repro.kernels.spectral_conv import (
    cached_weight_planes,
    spectral_apply,
    spectral_apply_fused,
    spectral_apply_fused_add,
    spectral_static_contribution,
)


@dataclasses.dataclass(frozen=True)
class FNOConfig:
    grid: Tuple[int, int, int, int]  # (nx, ny, nz, nt) of the solution tensor
    modes: Tuple[int, int, int, int]  # (mx, my, mz, mt); 2m kept per full dim
    width: int = 32
    in_channels: int = 1
    out_channels: int = 1
    n_blocks: int = 4
    decoder_dim: int = 128
    # Compute dtype for pointwise/conv ops; the FFT path is always f32.
    dtype: jnp.dtype = jnp.float32
    # Route the spectral conv through the fused Pallas kernel (truncate +
    # complex channel-mix + pad in one HBM pass); equivalence-gated against
    # the unfused path in tests/distributed_checks.py.
    use_pallas: bool = False
    # Channel-chunk the distributed FFT pipelines so each chunk's
    # all-to-all overlaps the next chunk's local FFTs (bit-identical; >1
    # only helps under a latency-hiding scheduler; unmeasured on a TPU).
    comm_chunks: int = 1
    remat: bool = True        # checkpoint each FNO block (A100-80GB -> v5e-16GB)

    @property
    def mode_shape(self) -> Tuple[int, int, int, int]:
        mx, my, mz, mt = self.modes
        return (2 * mx, 2 * my, 2 * mz, mt)

    def validate_for_parallelism(self, n_shards: int) -> None:
        nx = self.grid[0]
        two_my = 2 * self.modes[1]
        if nx % n_shards:
            raise ValueError(f"nx={nx} not divisible by {n_shards} shards")
        if two_my % n_shards:
            raise ValueError(f"2*my={two_my} not divisible by {n_shards} shards")
        self._validate_modes_fit()

    def validate_for_parallelism_2d(self, n_x: int, n_y: int) -> None:
        """Pencil decomposition: x sharded n_x ways, y sharded n_y ways.

        The two repartitions move the x-shard onto the truncated y dim and
        the y-shard onto the truncated z dim, hence the 2my/2mz constraints.
        """
        nx, ny = self.grid[0], self.grid[1]
        two_my, two_mz = 2 * self.modes[1], 2 * self.modes[2]
        if nx % n_x:
            raise ValueError(f"nx={nx} not divisible by {n_x} x-shards")
        if two_my % n_x:
            raise ValueError(f"2*my={two_my} not divisible by {n_x} x-shards")
        if ny % n_y:
            raise ValueError(f"ny={ny} not divisible by {n_y} y-shards")
        if two_mz % n_y:
            raise ValueError(f"2*mz={two_mz} not divisible by {n_y} y-shards")
        self._validate_modes_fit()

    def _validate_modes_fit(self) -> None:
        mx, my, mz, mt = self.modes
        nx, ny, nz, nt = self.grid
        if 2 * mx > nx or 2 * my > ny or 2 * mz > nz or mt > nt // 2 + 1:
            raise ValueError(f"modes {self.modes} exceed grid {self.grid}")


def init_params(key: jax.Array, cfg: FNOConfig) -> dict:
    """Initialize the FNO parameter pytree (block params stacked for scan)."""
    keys = jax.random.split(key, 6)
    w = cfg.width
    kshape = cfg.mode_shape
    scale = 1.0 / (w * w)
    spec_shape = (cfg.n_blocks, w, w) + kshape

    def uniform(k, shape, scale, dtype=jnp.float32):
        return jax.random.uniform(k, shape, dtype, -1.0, 1.0) * scale

    kr, ki = jax.random.split(keys[2])
    return {
        "encoder": {
            "w": uniform(keys[0], (cfg.in_channels, w), (1.0 / cfg.in_channels) ** 0.5),
            "b": jnp.zeros((w,), jnp.float32),
        },
        "blocks": {
            # complex64 spectral weights, the memory-dominant tensor
            "w_spec": (
                uniform(kr, spec_shape, scale) + 1j * uniform(ki, spec_shape, scale)
            ).astype(jnp.complex64),
            "w_bypass": uniform(keys[3], (cfg.n_blocks, w, w), (1.0 / w) ** 0.5),
            "b_bypass": jnp.zeros((cfg.n_blocks, w), jnp.float32),
        },
        "decoder": {
            "w1": uniform(keys[4], (w, cfg.decoder_dim), (1.0 / w) ** 0.5),
            "b1": jnp.zeros((cfg.decoder_dim,), jnp.float32),
            "w2": uniform(keys[5], (cfg.decoder_dim, cfg.out_channels), (1.0 / cfg.decoder_dim) ** 0.5),
            "b2": jnp.zeros((cfg.out_channels,), jnp.float32),
        },
    }


# ---------------------------------------------------------------------------
# Mesh layouts: the one place that reads the shape of ``model_axis``.
# ---------------------------------------------------------------------------

class _Layout(NamedTuple):
    forward: Callable    # the transform pair the blocks run
    adjoint: Callable
    axes: tuple          # the pair's axis arguments: () serial, (name,), (pair,)
    x: P                 # solution tensor [b, c, x, y, z, t]
    w: P                 # spectral weights [n_blocks, ci, co, kx, ky, kz, kt]
    contrib: P           # cached kept-mode contribution [b, co, kx, ky, kz, kt]
    validate: Callable   # validate(cfg, mesh): the divisibility the pair needs


def _layout(dp_axes, model_axis) -> _Layout:
    """The layout of ``model_axis``: None (pure data parallelism, the serial
    pair, everything but the batch replicated), one mesh-axis name (paper
    Alg. 2: x sharded, spectral weights along k_y) or a pair of names (the
    2-D pencil: x and y sharded, spectral weights along k_y x k_z — the
    dims each shard lands on after the pencil forward's repartitions). The
    batch dim goes over ``dp_axes``."""
    if model_axis is None:
        return _Layout(
            dfft.serial_forward, dfft.serial_adjoint, (),
            P(dp_axes, None, None, None, None, None), P(),
            P(dp_axes, None, None, None, None, None),
            lambda cfg, mesh: None,
        )
    if isinstance(model_axis, (tuple, list)):
        axes = tuple(model_axis)
        if len(axes) != 2:
            raise ValueError(f"expected 2 model axes, got {axes}")
        forward, adjoint, (ax_x, ax_y) = dfft.dist_forward_2d, dfft.dist_adjoint_2d, axes

        def validate(cfg, mesh):
            cfg.validate_for_parallelism_2d(mesh.shape[ax_x], mesh.shape[ax_y])
    else:
        forward, adjoint, axes = dfft.dist_forward, dfft.dist_adjoint, model_axis
        ax_x, ax_y = model_axis, None

        def validate(cfg, mesh):
            cfg.validate_for_parallelism(mesh.shape[ax_x])
    return _Layout(
        forward, adjoint, (axes,),
        P(dp_axes, None, ax_x, ax_y, None, None),
        P(None, None, None, None, ax_x, ax_y, None),
        P(dp_axes, None, None, ax_x, ax_y, None),
        validate,
    )


def param_specs(mesh: Mesh, model_axis="model", *, planes: bool = False) -> dict:
    """PartitionSpecs: spectral weights sharded as ``model_axis``'s layout
    says (``_layout``); encoder/decoder/bypass replicated (the paper's
    broadcast B).

    ``planes=True`` describes the plane-cached params tree
    (``params_with_planes``): ``w_spec`` replaced by float32
    ``w_spec_re``/``w_spec_im`` leaves. The planes keep the mode dims
    unflattened, so they take the SAME spec as the complex original.
    """
    del mesh
    w_spec = _layout(None, model_axis).w
    if planes:
        spec_leaves = {"w_spec_re": w_spec, "w_spec_im": w_spec}
    else:
        spec_leaves = {"w_spec": w_spec}
    return {
        "encoder": {"w": P(), "b": P()},
        "blocks": {
            **spec_leaves,
            "w_bypass": P(),
            "b_bypass": P(),
        },
        "decoder": {"w1": P(), "b1": P(), "w2": P(), "b2": P()},
    }


def input_spec(dp_axes, model_axis) -> P:
    """PartitionSpec of the solution tensor [b, c, x, y, z, t]: batch over
    the data axes, x (and y, for a pencil pair) over the model axes. The
    single source of truth for ``forward_and_specs``' in/out layout — reuse
    it wherever explicit in_shardings must match the shard_map'd forward.
    ``model_axis=None`` shards the batch dim only (pure data parallelism)."""
    return _layout(dp_axes, model_axis).x


def contrib_spec(dp_axes, model_axis) -> P:
    """PartitionSpec of the cached kept-mode contribution
    [b, co, 2mx, 2my, 2mz, mt]: batch over the data axes, k_y over the
    model axis (matching ``w_spec``'s sharding, since the contribution is a
    per-mode product with it) — and k_z over the second axis of a pencil
    pair. ``model_axis=None`` shards the batch dim only."""
    return _layout(dp_axes, model_axis).contrib


def params_with_planes(params: dict) -> dict:
    """Replace the complex ``w_spec`` with cached float32 re/im planes.

    For frozen params (serving): the re/im split the Pallas kernels need
    is computed ONCE per checkpoint (via the weight-plane cache) instead
    of once per block per rollout step, and the complex original is
    dropped from the tree so device memory is not doubled. The planes
    shard with the same PartitionSpecs (``param_specs(..., planes=True)``).
    """
    blocks = dict(params["blocks"])
    if "w_spec" not in blocks:
        return params
    w = blocks.pop("w_spec")
    wr, wi = cached_weight_planes(w)
    blocks["w_spec_re"] = wr
    blocks["w_spec_im"] = wi
    return {**params, "blocks": blocks}


def params_without_planes(params: dict) -> dict:
    """Inverse of ``params_with_planes``: recombine planes to complex
    ``w_spec`` (used by the serving --verify oracle, which replays through
    the plain serial forward)."""
    blocks = dict(params["blocks"])
    if "w_spec" in blocks:
        return params
    wr = blocks.pop("w_spec_re")
    wi = blocks.pop("w_spec_im")
    blocks["w_spec"] = wr + 1j * wi
    return {**params, "blocks": blocks}


def _block_weights(blk: dict):
    """Per-block spectral weights from a scan slice of params['blocks']:
    the complex ``w_spec`` or, for plane-cached params, the (re, im)
    tuple both ``spectral_apply`` and ``spectral_apply_fused`` accept."""
    if "w_spec" in blk:
        return blk["w_spec"]
    return (blk["w_spec_re"], blk["w_spec_im"])


# The model computes in float32: on a TPU a matmul's default precision is
# one bf16 pass, so every contraction asks for full float32 explicitly.
F32 = jax.lax.Precision.HIGHEST


def _conv1x1(x: jax.Array, w: jax.Array, b: Optional[jax.Array]) -> jax.Array:
    """Channel-mixing 1x1 conv on [b, c, x, y, z, t]."""
    y = jnp.einsum("bixyzt,io->boxyzt", x, w.astype(x.dtype), precision=F32)
    if b is not None:
        y = y + b.astype(x.dtype)[None, :, None, None, None, None]
    return y


@jax.named_scope("encoder")
def _encoder(params: dict, x: jax.Array, cfg: FNOConfig) -> jax.Array:
    x = x.astype(cfg.dtype)
    return jax.nn.gelu(_conv1x1(x, params["encoder"]["w"], params["encoder"]["b"]))


def encoder_prelift(params: dict, x: jax.Array, cfg: FNOConfig, channels=None) -> jax.Array:
    """Partial pre-activation lift of a channel SLICE of the input.

    The encoder's 1x1 conv is linear in x, so the lift of the static
    (geomodel) channels and the lift of the dynamic (well/state) channels
    can be computed independently and summed before bias + GELU. This is
    what lets serving cache the static-channel lift across requests and
    rollout steps (``serve.geomodel_cache``): precompute
    ``encoder_prelift(params, x_static, cfg, slice(0, n_static))`` once per
    geomodel, then only the dynamic slice is lifted per request.

    ``x``: [b, c_sub, nx, ny, nz, nt] where c_sub matches ``channels``
    (a slice into ``in_channels``; default: all). Returns the
    pre-activation partial sum [b, width, ...] — no bias, no GELU.
    """
    w = params["encoder"]["w"]
    if channels is not None:
        w = w[channels]
    x = x.astype(cfg.dtype)
    return jnp.einsum("bixyzt,io->boxyzt", x, w.astype(x.dtype), precision=F32)


def _encoder_from_prelift(params: dict, pre: jax.Array, cfg: FNOConfig) -> jax.Array:
    """bias + GELU over a (summed) pre-activation lift."""
    b = params["encoder"]["b"].astype(pre.dtype)
    return jax.nn.gelu(pre + b[None, :, None, None, None, None])


@jax.named_scope("decoder")
def _decoder(params: dict, x: jax.Array, cfg: FNOConfig) -> jax.Array:
    d = params["decoder"]
    h = jax.nn.gelu(_conv1x1(x, d["w1"], d["b1"]))
    out = _conv1x1(h, d["w2"], d["b2"])
    return out.astype(jnp.float32)


def _bypass(x, w_b, b_b):
    return _conv1x1(x, w_b, b_b)


@jax.named_scope("encoder")
def _split_lift(params: dict, pre_static: jax.Array, x_dyn: jax.Array,
                cfg: FNOConfig, n_static: int) -> jax.Array:
    """First hidden state from a cached static-channel prelift and the
    normalized dynamic channels, lifted here."""
    pre = pre_static.astype(cfg.dtype) + encoder_prelift(
        params, x_dyn, cfg, slice(n_static, None)
    )
    return _encoder_from_prelift(params, pre, cfg)


def fno_block(x, w_spec, w_b, b_b, cfg: FNOConfig, *, model_axis=None,
              add_kept=None, bypass_x=None):
    """One FNO block: adjoint(pad(W . trunc(forward(x)))) + bypass, GELU,
    on the transform pair of ``model_axis``'s layout (call inside
    shard_map for a mesh layout). Each stage runs under its own named scope
    (``fft_fwd`` with the pair's all-to-alls, ``mix``, ``fft_inv``,
    ``bypass``), so that a profile gives every op of a block to a stage.

    The transforms truncate y, z and t (on a mesh, before the
    repartition); with ``use_pallas`` the fused kernel does S_x / W· / S_x^T,
    so x crosses HBM at full size only there.

    The deep-cache forward passes ``add_kept``, a cached kept-mode
    contribution (on a mesh, this shard's modes, as ``contrib_spec``
    places them) summed into the spectral output before the inverse
    transform, and ``bypass_x``, the full activation the 1x1 bypass runs
    on when ``x`` is only the dynamic remainder.
    """
    lay = _layout(None, model_axis)
    comm = {"comm_chunks": cfg.comm_chunks} if lay.axes else {}
    fused = cfg.use_pallas
    kernel_dims = (cfg.grid[0], None, None)
    with jax.named_scope("fft_fwd"):
        xf = lay.forward(x, cfg.modes, *lay.axes, trunc_x=not fused, **comm)
    with jax.named_scope("mix"):
        if not fused:
            yf = spectral_apply(xf, w_spec, use_pallas=False)
            if add_kept is not None:
                yf = yf + add_kept.astype(yf.dtype)
        elif add_kept is None:
            yf = spectral_apply_fused(xf, w_spec, kernel_dims)
        else:
            yf = spectral_apply_fused_add(xf, w_spec, add_kept, kernel_dims)
    with jax.named_scope("fft_inv"):
        y = lay.adjoint(yf, cfg.grid, *lay.axes, out_dtype=cfg.dtype,
                        pad_x=not fused, **comm)
    with jax.named_scope("bypass"):
        xb = x if bypass_x is None else bypass_x
        return jax.nn.gelu(y + _bypass(xb, w_b, b_b))


def _run_blocks(params: dict, h: jax.Array, cfg: FNOConfig, block_apply):
    """Shared tail of every forward: scan the FNO blocks, then decode.
    ``block_apply(h, blk)`` applies one block's params to the hidden state."""

    def body(h, blk):
        return block_apply(h, blk), None

    if cfg.remat:
        body = jax.checkpoint(body)
    with jax.named_scope("blocks"):
        h, _ = jax.lax.scan(body, h, params["blocks"])
    return _decoder(params, h, cfg)


def fno_forward(
    params: dict,
    x: jax.Array,
    cfg: FNOConfig,
    *,
    model_axis=None,
    pre_static: Optional[jax.Array] = None,
    n_static: int = 0,
    contrib: Optional[jax.Array] = None,
) -> jax.Array:
    """The FNO forward. x: [b, c_in, nx, ny, nz, nt] -> [b, c_out, ...].

    ``model_axis``: the layout (``_layout``) whose transform pair the
    blocks run; a mesh layout is called inside shard_map
    (``forward_and_specs``), on this shard's part of every operand.
    Encoder and decoder weights are replicated (the paper's broadcast B) and
    contract channels only, so they need no communication (paper Alg. 1).

    ``pre_static``: [b, width, ...] — the cached partial lift of the first
    ``n_static`` input channels (``encoder_prelift`` over the NORMALIZED
    static channels); ``x`` then holds only the normalized dynamic
    channels [b, in_channels - n_static, ...], lifted here. Equal to the
    forward on the concatenated input up to float-summation order.

    ``contrib`` (with ``pre_static``): [b, width, 2mx, 2my, 2mz, mt]
    complex — block 0's kept-mode static contribution ``W_0 . S(h_static)``
    (``spectral_prelift``). Block 0 then runs on the dynamic REMAINDER
    ``h - h_static``, its static term summed in before the inverse
    transform, and the remaining blocks run unchanged.

    Cold and warm cache paths feed identical host-computed operands into
    the same program, so they are bit-identical to each other.
    """
    ck = None if contrib is None else contrib.astype(jnp.complex64)

    def block(h, blk, **kw):
        return fno_block(h, _block_weights(blk), blk["w_bypass"], blk["b_bypass"],
                         cfg, model_axis=model_axis, **kw)

    if pre_static is None:
        h = _encoder(params, x, cfg)
    else:
        h = _split_lift(params, pre_static, x, cfg, n_static)
    if ck is not None:
        with jax.named_scope("encoder"):
            h_static = _encoder_from_prelift(params, pre_static.astype(cfg.dtype), cfg)
            h_rem = h - h_static
        blocks = params["blocks"]
        with jax.named_scope("blocks"):
            blk0 = jax.tree.map(lambda a: a[0], blocks)
            h = block(h_rem, blk0, add_kept=ck, bypass_x=h)
            params = {**params, "blocks": jax.tree.map(lambda a: a[1:], blocks)}
    return _run_blocks(params, h, cfg, block)


def spectral_prelift(params: dict, pre_static: jax.Array, cfg: FNOConfig, *, block: int = 0):
    """Static prefix of the FIRST spectral block, computed once per geomodel.

    The block-input split: write the first hidden state as
    ``h = h_static + h_rem`` with ``h_static = GELU(pre_static + b)`` a pure
    function of the cached static-channel prelift. FFT -> truncate -> mix is
    linear, so block 0's kept-mode output is
    ``W . S(h_rem)  +  W . S(h_static)`` — and the second term (and its
    spectrum) can be cached alongside the prelift and summed into the
    dynamic remainder's pre-activation on every warm request
    (``fno_forward(..., contrib=...)``). The nonlinearity after block 0
    stops the split from going deeper.

    ``pre_static``: [b, width, nx, ny, nz, nt] (or unbatched [width, ...]).
    Returns ``(spectra, contribution)``: the truncated kept-mode spectrum
    S(h_static) [.., width, 2mx, 2my, 2mz, mt] and the weight-mixed
    contribution W_block . S(h_static) of the same shape — cache levels L3
    and L4 of ``serve.geomodel_cache``.
    """
    unbatched = pre_static.ndim == 5
    if unbatched:
        pre_static = pre_static[None]
    h_s = _encoder_from_prelift(params, pre_static.astype(cfg.dtype), cfg)
    spectra = dfft.serial_forward(h_s, cfg.modes)
    blk = jax.tree.map(lambda a: a[block], params["blocks"])
    contrib = spectral_static_contribution(spectra, _block_weights(blk))
    if unbatched:
        spectra, contrib = spectra[0], contrib[0]
    return spectra, contrib


def forward_and_specs(
    mesh: Mesh,
    cfg: FNOConfig,
    *,
    dp_axes=("data",),
    model_axis=None,
    planes: bool = False,
    n_static: int = 0,
    prefix: Optional[str] = None,
):
    """(forward, x_spec, p_specs) for a mesh: the single source of truth for
    how an FNO batch and its params are laid out, shared by the training
    driver and the serving runner.

    ``model_axis`` picks the layout (``_layout``): None gives ``fno_forward``
    itself (pure data parallelism: params replicated, batch sharded over
    ``dp_axes``); a mesh-axis name or a pair of names gives the shard_map
    of the same body on the layout's pair (paper Alg. 2 / 2-D pencils).

    ``prefix`` picks the lift: ``forward(params, x)`` for None,
    ``forward(params, pre_static, x_dyn)`` for ``"prelift"`` and
    ``forward(params, contrib, pre_static, x_dyn)`` for ``"deep"``, with
    ``n_static`` cached channels (see ``fno_forward``). ``pre_static`` and
    ``x_dyn`` take ``x_spec`` (the channel dim is never sharded);
    ``contrib`` takes ``contrib_spec(dp_axes, model_axis)``.

    ``planes=True``: specs and shard_map layouts for a plane-cached params
    tree (``params_with_planes``, serving only).
    """
    lay = _layout(dp_axes, model_axis)
    p_specs = param_specs(mesh, model_axis, planes=planes)
    kw = {"model_axis": model_axis, "n_static": n_static}
    if prefix is None:
        def forward(params, x):
            return fno_forward(params, x, cfg, **kw)
        cached = ()
    elif prefix == "prelift":
        def forward(params, pre_static, x_dyn):
            return fno_forward(params, x_dyn, cfg, pre_static=pre_static, **kw)
        cached = (lay.x,)
    elif prefix == "deep":
        def forward(params, contrib, pre_static, x_dyn):
            return fno_forward(params, x_dyn, cfg, pre_static=pre_static,
                               contrib=contrib, **kw)
        cached = (lay.contrib, lay.x)
    else:
        raise ValueError(f"prefix must be None, 'prelift' or 'deep', got {prefix!r}")
    if not lay.axes:
        return forward, lay.x, p_specs
    lay.validate(cfg, mesh)

    # jitted, this is the program ``jit_shard_fwd`` (profiles' op paths start
    # with it), its parameters named after forward's arguments
    @functools.wraps(forward, assigned=())
    def shard_fwd(*args):
        return forward(*args)

    in_specs = (p_specs, *cached, lay.x)
    return compat.shard_map(shard_fwd, mesh, in_specs, lay.x), lay.x, p_specs


def mse_loss(pred: jax.Array, target: jax.Array) -> jax.Array:
    return jnp.mean(jnp.square(pred.astype(jnp.float32) - target.astype(jnp.float32)))
