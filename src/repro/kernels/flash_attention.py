"""Pallas TPU flash-attention kernel (forward).

TPU-native blocking: q tiles of (BLOCK_Q, head_dim) live in VMEM and loop
over kv tiles of (BLOCK_K, head_dim) on the MXU, maintaining the online
softmax (m, l, acc) in VREGs/VMEM — the FlashAttention algorithm re-tiled
for the HBM->VMEM->MXU hierarchy rather than CUDA shared memory (DESIGN.md
"hardware adaptation").  Tiles are multiples of 128 to match MXU/VPU lane
dims.  Grid: (batch*heads, Sq/BLOCK_Q); the kv loop is a fori_loop inside
the kernel so kv tiles stream through VMEM.

Validated in interpret mode against kernels/ref.py on CPU (tests/
test_kernels.py); the backward pass reuses the custom-VJP recompute of
flash_attention_ref (fwd-kernel + recompute-bwd is the standard serving
configuration; a Pallas bwd kernel is a further optimization).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  window: int | None, q_offset: int, scale: float,
                  seq_kv: int):
    """One (bh, q_block) grid cell.  Refs: q (BQ,hd); k/v (Skv,hd)."""
    block_q, hd = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    q_base = pl.program_id(1) * block_q + q_offset
    q_pos = q_base + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(i, carry):
        m, l, acc = carry
        ks = k_ref[pl.dslice(i * block_k, block_k), :]
        vs = v_ref[pl.dslice(i * block_k, block_k), :]
        s = jax.lax.dot_general(q, ks.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, vs.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    n_kv = seq_kv // block_k
    if causal and window is None:
        # skip fully-masked kv tiles: only blocks with k_base <= q_max
        q_max = q_base + block_q - 1
        n_eff = jnp.minimum(n_kv, (q_max // block_k) + 1)
    else:
        n_eff = n_kv
    m, l, acc = jax.lax.fori_loop(0, n_eff, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "window", "q_offset",
                                             "interpret"))
def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    causal: bool = True, window: int | None = None,
                    q_offset: int = 0, interpret: bool = False):
    """q: (B,Sq,H,hd); k,v: (B,Skv,H,hd) with H already GQA-expanded.
    Sq % block_q == 0 and Skv % block_k == 0 (pad upstream)."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    scale = hd ** -0.5
    # fold batch and heads into the grid's leading dim
    qr = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, hd)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * h, skv, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * h, skv, hd)

    kernel = functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                               window=window, q_offset=q_offset, scale=scale,
                               seq_kv=skv)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((None, skv, hd), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((None, skv, hd), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return jnp.moveaxis(out.reshape(b, h, sq, hd), 1, 2)
