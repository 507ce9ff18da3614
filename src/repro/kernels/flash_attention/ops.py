"""Public wrapper for flash attention: padding, masking, dispatch.

``use_pallas=False`` (default on CPU / in AOT dry-runs) routes to a chunked
XLA online-softmax implementation with identical math — the dry-run roofline
then reflects flash-style memory behaviour, and the TPU runtime can flip to
the Pallas kernel without changing call sites.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.interpret import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    use_pallas: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    chunk_k: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """q: [b, h, sq, d]; k/v: [b, kvh, sk, d] -> [b, h, sq, d]."""
    if not use_pallas:
        return attention_chunked(q, k, v, causal=causal, scale=scale, chunk_k=chunk_k)

    interpret = resolve_interpret(interpret)
    sq, sk = q.shape[2], k.shape[2]
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    # The kernel masks key positions >= true_sk and keeps the causal offset
    # aligned to the TRUE lengths; padded query rows are sliced off below.
    o = flash_attention_pallas(
        qp, kp, vp,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, true_sq=sq, true_sk=sk,
    )
    return o[:, :, :sq] if pad_q else o


def attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    chunk_k: int = 1024,
) -> jax.Array:
    """XLA online-softmax attention: scans kv in chunks, never builds SxS.

    Used for long sequences in training/prefill (the memory-roofline fix)
    and as the dry-run stand-in for the Pallas kernel.
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if h != kvh:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = d ** -0.5
    if sk <= chunk_k:
        return attention_ref(q, k, v, causal=causal, scale=scale)
    true_sk = sk
    if sk % chunk_k:
        pad = chunk_k - sk % chunk_k
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        sk = k.shape[2]
    n_chunks = sk // chunk_k
    kc = k.reshape(b, h, n_chunks, chunk_k, d)
    vc = v.reshape(b, h, n_chunks, chunk_k, d)
    qf = q.astype(jnp.float32)
    qpos = jnp.arange(sq) + (true_sk - sq)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        kci, vci, ci = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kci.astype(jnp.float32)) * scale
        kpos = ci * chunk_k + jnp.arange(chunk_k)
        mask = kpos[None, :] < true_sk  # padded tail keys
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        s = jnp.where(mask, s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vci.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, acc0),
        (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0), jnp.arange(n_chunks)),
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
