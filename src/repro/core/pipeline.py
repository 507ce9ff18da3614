"""GPipe-style pipeline-parallel FNO — the paper's comparison baseline.

The paper (Fig. 6/7) shows pipeline parallelism reaches <=50% parallel
efficiency on the FNO (no concurrency at batch size 1, bubble-bound at small
microbatch counts) while domain decomposition exceeds 90%. To reproduce that
comparison we implement an honest GPipe schedule in JAX:

  * the n_blocks FNO blocks are the pipeline stages, one per device on the
    ``model`` axis (block params sharded on their leading stacked dim);
  * the batch is split into M microbatches; a shard_map loop advances the
    pipeline with ``jax.lax.ppermute`` (stage i -> i+1) each tick;
  * encoder/decoder (cheap 1x1 convs) run replicated outside the pipe;
  * bubble fraction = (P-1)/(M+P-1), which is the quantity the paper's
    Fig. 6 measures indirectly (50% efficiency at P=2, M=1, etc.).

Backward works through ``jax.grad`` (ppermute transposes to the reverse
permutation), so train-step comparisons DD-vs-PP are possible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.common import compat
from repro.core import fno as fno_lib
from repro.core.fno import FNOConfig


def _pipeline_blocks(blocks, h_micro, cfg: FNOConfig, axis_name: str):
    """Run microbatches through the block pipeline. Call inside shard_map.

    blocks: this stage's block params (leading n_blocks dim already sharded
      to size 1 by shard_map) — squeezed inside.
    h_micro: [M, mb, width, nx, ny, nz, nt] replicated microbatch stack.
    Returns the same stack after all blocks, replicated via psum.
    """
    p = compat.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = h_micro.shape[0]
    w_spec = blocks["w_spec"][0]
    w_b = blocks["w_bypass"][0]
    b_b = blocks["b_bypass"][0]

    perm = [(i, i + 1) for i in range(p - 1)]
    n_ticks = m + p - 1
    zeros = jnp.zeros_like(h_micro[0])

    def tick(carry, t):
        recv, outs = carry
        inp = jnp.where(t < m, h_micro[jnp.minimum(t, m - 1)], zeros)
        h_in = jnp.where(stage == 0, inp, recv)
        y = fno_lib.fno_block(h_in, w_spec, w_b, b_b, cfg)
        recv = jax.lax.ppermute(y, axis_name, perm)
        # Last stage emits microbatch t-(p-1) at tick t.
        out_idx = t - (p - 1)
        is_out = jnp.logical_and(stage == p - 1, out_idx >= 0)
        outs = jax.lax.cond(
            out_idx >= 0,
            lambda o: o.at[jnp.maximum(out_idx, 0)].set(
                jnp.where(is_out, y, o[jnp.maximum(out_idx, 0)])
            ),
            lambda o: o,
            outs,
        )
        return (recv, outs), None

    outs0 = jnp.zeros_like(h_micro)
    with jax.named_scope("blocks"):
        (_, outs), _ = jax.lax.scan(tick, (zeros, outs0), jnp.arange(n_ticks))
    # Only the last stage holds real outputs; broadcast to all stages.
    outs = jnp.where(stage == p - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)


def make_pipeline_forward(
    mesh: Mesh,
    cfg: FNOConfig,
    *,
    n_micro: int,
    model_axis: str = "model",
):
    """Build jit-able pipeline forward: (params, x[b,...]) -> y[b,...].

    Requires cfg.n_blocks == mesh size along the model axis and
    batch % n_micro == 0.
    """
    p = mesh.shape[model_axis]
    if cfg.n_blocks != p:
        raise ValueError(
            f"pipeline needs n_blocks == stages ({cfg.n_blocks} != {p})"
        )

    block_specs = {
        "w_spec": P(model_axis, None, None, None, None, None, None),
        "w_bypass": P(model_axis, None, None),
        "b_bypass": P(model_axis, None),
    }

    def fwd(params, x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        h = fno_lib._encoder(params, x, cfg)
        h_micro = h.reshape((n_micro, b // n_micro) + h.shape[1:])

        piped = compat.shard_map(
            lambda blocks, hm: _pipeline_blocks(blocks, hm, cfg, model_axis),
            mesh,
            (block_specs, P()),
            P(),
        )(params["blocks"], h_micro)

        h = piped.reshape((b,) + piped.shape[2:])
        return fno_lib._decoder(params, h, cfg)

    return fwd


def bubble_efficiency(p: int, n_micro: int) -> float:
    """Ideal GPipe parallel efficiency: M / (M + P - 1)."""
    return n_micro / (n_micro + p - 1)
