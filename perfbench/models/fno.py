"""The FNO family as the benchmark sees it: weights from a seed, scenario
inputs from a seed, a plain float32 reference, and the operation counts.

Nothing here imports the program. The reference follows the paper's
Algorithm 1 (arXiv:2211.12709): a 1x1-conv encoder with GELU, ``n_blocks``
blocks of ``GELU(irfftn(pad(W . trunc(rfftn(h)))) + bypass(h))``, and a
two-layer 1x1-conv decoder. Two choices follow the program under test, so
that both compute the same function:

* GELU is the tanh approximation (what ``jax.nn.gelu`` computes by
  default);
* the kept modes of a full FFT axis are the ``m`` lowest and the ``m``
  highest bins, in that order; of the rFFT axis, the first ``m_t`` bins.

Every contraction runs at the precision it is asked for: ``highest``
(float32, what the configuration states) or ``high`` (three bf16 passes,
emulated explicitly so that the CPU computes the same as the chip). FFTs
are float32 in both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


# -- seeds ------------------------------------------------------------------

def jax_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative seed (a seed may exceed 32 bits)."""
    word = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def np_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


# -- weights ----------------------------------------------------------------

def mode_shape(cfg: dict) -> tuple:
    mx, my, mz, mt = cfg["modes"]
    return (2 * mx, 2 * my, 2 * mz, mt)


def make_params(key: jax.Array, cfg: dict) -> dict:
    """The parameter tree the program takes, drawn from ``key``: uniform
    weights at the usual FNO scales, complex64 spectral weights, zero
    biases. Call it under ``jit`` so it runs on the device."""
    k = jax.random.split(key, 6)
    w, d = cfg["width"], cfg["decoder_dim"]
    c_in, c_out, nb = cfg["in_channels"], cfg["out_channels"], cfg["n_blocks"]
    spec_shape = (nb, w, w) + mode_shape(cfg)

    def uniform(key, shape, scale):
        return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) * scale

    kr, ki = jax.random.split(k[2])
    s = 1.0 / (w * w)
    return {
        "encoder": {"w": uniform(k[0], (c_in, w), c_in ** -0.5),
                    "b": jnp.zeros((w,), jnp.float32)},
        "blocks": {
            "w_spec": (uniform(kr, spec_shape, s)
                       + 1j * uniform(ki, spec_shape, s)).astype(jnp.complex64),
            "w_bypass": uniform(k[3], (nb, w, w), w ** -0.5),
            "b_bypass": jnp.zeros((nb, w), jnp.float32),
        },
        "decoder": {"w1": uniform(k[4], (w, d), w ** -0.5),
                    "b1": jnp.zeros((d,), jnp.float32),
                    "w2": uniform(k[5], (d, c_out), d ** -0.5),
                    "b2": jnp.zeros((c_out,), jnp.float32)},
    }


# -- scenario inputs --------------------------------------------------------

def log_permeability(grid3, seed: int) -> np.ndarray:
    """Sleipner-like layered log-permeability [nx, ny, nz]: lognormal
    background, a smooth vertical layering, and thin shale streaks every
    third layer (the two-phase generator's construction)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    nx, ny, nz = grid3
    base = rng.lognormal(mean=0.0, sigma=0.4, size=(nx, ny, nz))
    k = base * np.exp(0.8 * np.sin(np.linspace(0, 3 * np.pi, nz)))[None, None, :]
    k[:, :, 2::3] *= 0.05
    return np.log(k).astype(np.float32)


def well_positions(grid3, n_wells: int, rng: np.random.Generator) -> tuple:
    """Sorted (i, j) injector columns, two cells clear of the x/y border."""
    nx, ny, _ = grid3
    return tuple(sorted(
        (int(rng.integers(2, nx - 2)), int(rng.integers(2, ny - 2)))
        for _ in range(n_wells)
    ))


def well_map(grid3, wells) -> np.ndarray:
    """Binary injector map [nx, ny, nz], perforated in the bottom 3 cells."""
    m = np.zeros(grid3, np.float32)
    for i, j in wells:
        m[i, j, grid3[2] - 3:] = 1.0
    return m


def along_t(field3: np.ndarray, nt: int) -> np.ndarray:
    return np.repeat(field3[None, ..., None], nt, axis=-1)


def scenario_input(cfg: dict, logk: np.ndarray, wells) -> np.ndarray:
    """[in_channels, nx, ny, nz, nt]: the geomodel channel, then the well
    map in every remaining channel."""
    nt = cfg["grid"][3]
    grid3 = tuple(cfg["grid"][:3])
    w = along_t(well_map(grid3, wells), nt)
    n_dyn = cfg["in_channels"] - 1
    return np.concatenate([along_t(logk, nt)] + [w] * n_dyn, axis=0)


def training_target(x: np.ndarray, cfg: dict) -> np.ndarray:
    """A smooth nonlinear transform of the well channel [c_out, ...]: a
    learnable stand-in target (no simulator in the benchmark)."""
    w = x[1:2]
    y = 0.5 * np.tanh(np.roll(w, 1, axis=1) + 0.5 * np.roll(w, 2, axis=2))
    return np.repeat(y, cfg["out_channels"], axis=0).astype(np.float32)


def stats_arrays(stats: dict):
    """(mean, std) float32, shaped [1, c, 1, 1, 1, 1]."""
    shape = (1, -1, 1, 1, 1, 1)
    return (np.asarray(stats["mean"], np.float32).reshape(shape),
            np.asarray(stats["std"], np.float32).reshape(shape))


def feedback(y_raw: np.ndarray, n_channels: int) -> np.ndarray:
    """Next rollout input from a prediction [c_out, ...]: hold the final
    frame along t and fill ``n_channels`` channels with it."""
    nxt = np.repeat(y_raw[..., -1:], y_raw.shape[-1], axis=-1)
    reps = -(-n_channels // nxt.shape[0])
    return np.concatenate([nxt] * reps, axis=0)[:n_channels].astype(np.float32)


# -- the plain reference ----------------------------------------------------

def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _split_bf16(a):
    """``a``'s leading and trailing bfloat16 parts, held as float32.
    ``reduce_precision`` rounds to bfloat16's 8 exponent and 7 mantissa
    bits; a cast to bfloat16 and back would not do: the TPU compiler may
    drop such a round trip (excess precision), which leaves ``lo`` zero
    and the control one bf16 pass instead of three."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def contract(eq: str, a, b, precision: str):
    """Real einsum at ``highest`` (float32) or ``high`` (three bf16 passes,
    float32 accumulation: hi*hi + hi*lo + lo*hi). The bf16 parts are
    multiplied as float32, in which their products are exact, so the CPU
    (which has no bf16 dot) and the chip compute the same numbers."""
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def one(x, y):
        return jnp.einsum(eq, x, y, precision=jax.lax.Precision.HIGHEST)

    return one(ah, bh) + (one(ah, bl) + one(al, bh))


def _conv1x1(h, w, b, precision):
    return contract("bixyzt,io->boxyzt", h, w, precision) + b[None, :, None, None, None, None]


def _keep(xf, modes):
    mx, my, mz, mt = modes
    for ax, m in ((2, mx), (3, my), (4, mz)):
        n = xf.shape[ax]
        xf = jnp.concatenate([jax.lax.slice_in_dim(xf, 0, m, axis=ax),
                              jax.lax.slice_in_dim(xf, n - m, n, axis=ax)], axis=ax)
    return jax.lax.slice_in_dim(xf, 0, mt, axis=5)


def _pad(yk, grid):
    nx, ny, nz, nt = grid
    for ax, n in ((2, nx), (3, ny), (4, nz)):
        m = yk.shape[ax] // 2
        zshape = list(yk.shape)
        zshape[ax] = n - 2 * m
        yk = jnp.concatenate([jax.lax.slice_in_dim(yk, 0, m, axis=ax),
                              jnp.zeros(zshape, yk.dtype),
                              jax.lax.slice_in_dim(yk, m, 2 * m, axis=ax)], axis=ax)
    zshape = list(yk.shape)
    zshape[5] = nt // 2 + 1 - yk.shape[5]
    return jnp.concatenate([yk, jnp.zeros(zshape, yk.dtype)], axis=5)


def _spectral(h, w, cfg, precision):
    """The spectral convolution: transform, keep the low modes, mix the
    channels mode by mode, pad, transform back."""
    grid = tuple(cfg["grid"])
    xk = _keep(jnp.fft.fftn(jnp.fft.rfft(h, axis=5), axes=(2, 3, 4)), cfg["modes"])
    xr, xi = jnp.real(xk), jnp.imag(xk)
    wr, wi = jnp.real(w), jnp.imag(w)
    eq = "bixyzt,ioxyzt->boxyzt"
    yr = contract(eq, xr, wr, precision) - contract(eq, xi, wi, precision)
    yi = contract(eq, xr, wi, precision) + contract(eq, xi, wr, precision)
    yf = _pad(jax.lax.complex(yr, yi), grid)
    return jnp.fft.irfft(jnp.fft.ifftn(yf, axes=(2, 3, 4)), n=grid[3], axis=5)


def forward(params: dict, x: jax.Array, cfg: dict, precision: str = "highest"):
    """Reference forward: x [b, c_in, nx, ny, nz, nt] (normalized) ->
    [b, c_out, ...]. Each block is rematerialized in the backward pass."""
    enc, blocks, dec = params["encoder"], params["blocks"], params["decoder"]
    h = _gelu(_conv1x1(x, enc["w"], enc["b"], precision))

    @jax.checkpoint
    def block(h, w_spec, w_b, b_b):
        return _gelu(_spectral(h, w_spec, cfg, precision)
                     + _conv1x1(h, w_b, b_b, precision))

    for k in range(cfg["n_blocks"]):
        h = block(h, blocks["w_spec"][k], blocks["w_bypass"][k], blocks["b_bypass"][k])
    h = _gelu(_conv1x1(h, dec["w1"], dec["b1"], precision))
    return _conv1x1(h, dec["w2"], dec["b2"], precision)


def loss(params, x, y, cfg, precision="highest"):
    return jnp.mean(jnp.square(forward(params, x, cfg, precision) - y))


def adamw(params, grads, state, opt: dict):
    """AdamW with global-norm clipping; complex leaves keep a real second
    moment E[|g|^2]. Returns (new params, new state)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.abs(g) ** 2) for g in leaves))
    if opt.get("grad_clip") is not None:
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    count = state["count"] + 1
    c = count.astype(jnp.float32)
    b1, b2, lr, eps = opt["b1"], opt["b2"], opt["lr"], opt["eps"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * (jnp.abs(g) ** 2), state["nu"], grads)

    def upd(p, m, v):
        delta = (m / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + eps)
        new = p - (lr * delta).astype(p.dtype)
        if opt.get("weight_decay") and not jnp.iscomplexobj(p):
            new = new - lr * opt["weight_decay"] * p
        return new

    return (jax.tree.map(upd, params, mu, nu),
            {"mu": mu, "nu": nu, "count": count})


def adamw_init(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "count": jnp.zeros((), jnp.int32)}


# -- operation and byte counts ----------------------------------------------

def n_cells(cfg: dict) -> int:
    return math.prod(cfg["grid"])


def kept_modes(cfg: dict) -> int:
    return math.prod(mode_shape(cfg))


def fft_flops(cfg: dict) -> float:
    """One forward plus one inverse 4-D real transform of ``width``
    channels: 5 n log2 n per complex transform, half of that for the real
    axis. rFFT along t over every (x, y, z) line, then a complex 3-D FFT
    over (x, y, z) for each of the nt/2+1 bins."""
    nx, ny, nz, nt = cfg["grid"]
    m = nx * ny * nz
    one_way = m * 2.5 * nt * math.log2(nt) + (nt // 2 + 1) * 5 * m * math.log2(m)
    return 2 * cfg["width"] * one_way


def forward_flops(cfg: dict) -> dict:
    """Model FLOPs of one forward per sample, by part. GELU, bias adds
    and the truncation copies are not counted."""
    n, w, d = n_cells(cfg), cfg["width"], cfg["decoder_dim"]
    nb = cfg["n_blocks"]
    parts = {
        "encoder": 2 * cfg["in_channels"] * w * n,
        "bypass": nb * 2 * w * w * n,
        "fft": nb * fft_flops(cfg),
        "mix": nb * 8 * w * w * kept_modes(cfg),
        "decoder": 2 * (w * d + d * cfg["out_channels"]) * n,
    }
    parts["total"] = sum(parts.values())
    return parts


def train_step_flops(cfg: dict, batch: int) -> float:
    """Forward plus backward (twice the forward), recomputation not
    counted."""
    return 3 * forward_flops(cfg)["total"] * batch


def mix_work(cfg: dict, batch: int) -> tuple:
    """(ops, bytes) the spectral mix of ONE block needs at ``batch``: a
    complex multiply-add (8 real ops) per (sample, ci, co, kept mode); the
    complex64 weight read once, and the kept coefficients read and
    written once per sample."""
    w, k = cfg["width"], kept_modes(cfg)
    ops = 8 * w * w * k * batch
    nbytes = 8 * w * w * k + 2 * 8 * batch * w * k
    return float(ops), float(nbytes)
