"""Pallas TPU kernel for the RWKV6 (WKV) recurrence — chunked form.

The WKV recurrence S_t = diag(w_t) S_{t-1} + k_t^T v_t is sequential, but
within a chunk of C tokens the contribution of the chunk-initial state and
the intra-chunk pairs can be computed with dense matmuls (MXU-friendly):

    y_t = r_t (prod_{j<=t} w_j) S_0 + sum_{i<t} r_t (prod_{i<j<=t} w_j)
          k_i^T v_i + r_t (u * k_t^T v_t)

Grid: (batch*heads,); the kernel walks chunks with fori_loop, carrying the
(hd, hd) state in VMEM scratch.  Tiles sized (C=128, hd<=128) align with
the MXU.  Validated in interpret mode against kernels/ref.wkv6_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_scratch,
                *, chunk: int, seq: int):
    hd = r_ref.shape[-1]
    s_scratch[...] = jnp.zeros((hd, hd), jnp.float32)
    u = u_ref[...].astype(jnp.float32)                     # (1,hd)
    n_chunks = seq // chunk
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (the TPU lowering has no cumsum)
    tril = jnp.where(t_idx >= i_idx, 1.0, 0.0).astype(jnp.float32)

    def body(ci, _):
        sl = (pl.dslice(ci * chunk, chunk), slice(None))
        r = r_ref[sl].astype(jnp.float32)                  # (C,hd)
        k = k_ref[sl].astype(jnp.float32)
        v = v_ref[sl].astype(jnp.float32)
        w = w_ref[sl].astype(jnp.float32)
        logw = jnp.log(jnp.maximum(w, 1e-30))
        cum = jax.lax.dot_general(                         # (C,hd) inclusive
            tril, logw, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        cum_ex = cum - logw                                # exclusive: j < t
        # state contribution: r_t * prod_{j<t} w_j applied to S_0
        r_dec = r * jnp.exp(cum_ex)
        s0 = s_scratch[...]
        y_state = jax.lax.dot_general(r_dec, s0, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        # intra-chunk: A[t,i] = sum_d r[t,d] k[i,d] exp(cum_ex[t,d]-cum[i,d])
        # factorized as a masked matmul; normalize by the mid-chunk decay so
        # neither factor over/underflows (valid while the per-chunk decay
        # range stays within fp32 exponent headroom — chunk=128 with
        # realistic RWKV decays; see module docstring)
        # rows are taken as static (1,hd) slices: the TPU lowering has no
        # dynamic_slice, which integer indexing of a value would emit
        c_mid = jax.lax.slice_in_dim(cum, chunk // 2, chunk // 2 + 1)
        r_sc = r * jnp.exp(cum_ex - c_mid)                 # (C,hd)
        k_sc = k * jnp.exp(c_mid - cum)                    # (C,hd)
        att = jax.lax.dot_general(r_sc, k_sc, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        att = jnp.where(t_idx > i_idx, att, 0.0)           # strict past
        y_intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        # current-token bonus: r_t (u * k_t) v_t
        bonus = jnp.sum(r * u * k, axis=1, keepdims=True) * v
        y = y_state + y_intra + bonus
        y_ref[sl] = y.astype(y_ref.dtype)
        # carry state: S <- diag(prod w) S_0 + sum_i (prod_{j>i} w) k_i v_i
        cum_last = jax.lax.slice_in_dim(cum, chunk - 1, chunk)   # (1,hd)
        log_decay = jax.lax.dot_general(                   # (hd,1) column
            logw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        k_tail = k * jnp.exp(cum_last - cum)               # (C,hd)
        s_new = jnp.exp(log_decay) * s0 + jax.lax.dot_general(
            k_tail, v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s_scratch[...] = s_new
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """Chunk-parallel WKV6.  r,k,v,w: (B,S,H,hd); u: (H,hd).
    S % chunk == 0.  Returns y: (B,S,H,hd)."""
    b, s, h, hd = r.shape
    fold = lambda t: jnp.moveaxis(t, 2, 1).reshape(b * h, s, hd)  # noqa: E731
    rr, kk, vv, ww = fold(r), fold(k), fold(v), fold(w)
    uu = u.reshape(h, hd)
    # one (1, hd) row per grid step: a block's last two dims must equal the
    # array's (or tile by 8 x 128) for the TPU lowering
    uu = jnp.broadcast_to(uu[None], (b, h, hd)).reshape(b * h, 1, hd)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, seq=s)
    y = pl.pallas_call(
        kernel,
        grid=(b * h,),
        in_specs=[
            pl.BlockSpec((None, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, hd), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, s, hd), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, uu)
    return jnp.moveaxis(y.reshape(b, h, s, hd), 1, 2)
