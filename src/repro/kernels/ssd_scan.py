"""Chunked Mamba-2 SSD scan as a Pallas kernel — MX accumulation, generalized.

The SSD (state-space dual) computation
    h_t = a_t * h_{t-1} + outer(b_t, x_t);   y_t = c_t @ h_t
is evaluated chunk-by-chunk: three MXU matmuls per chunk (G = C Bᵀ, the
masked intra-chunk product, and the state update) plus a cheap (S, P)
recurrent state.

MX mapping: the recurrent state h lives in a **VMEM scratch that persists
across the chunk grid dimension** — the same inter-k-buffering idea as the
matmul accumulator (the reduction here is the time axis instead of K).  The
state is written back to HBM exactly zero times during the scan; the baseline
(non-MX) formulation would materialize h per chunk.

Grid: (num_chunks,) with "arbitrary" semantics (the state carries a
dependence).  All within-chunk math is f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, alog_ref, b_ref, c_ref, y_ref, h_ref, *, out_dtype):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():  # C-tile-reset analogue: zero initial state, no HBM load
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)  # (Q, P)
    alog = alog_ref[...].astype(jnp.float32)  # (Q, 1)
    b = b_ref[...].astype(jnp.float32)  # (Q, S)
    c = c_ref[...].astype(jnp.float32)  # (Q, S)
    h = h_ref[...]  # (S, P) f32

    acum = jnp.cumsum(alog, axis=0)  # (Q, 1) inclusive
    q = x.shape[0]
    # decay ratios: L[t, s] = exp(acum_t - acum_s) for s <= t else 0
    delta = acum - acum.reshape(1, q)  # (Q, Q) = acum_t - acum_s
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmask = row >= col
    decay = jnp.where(lmask, jnp.exp(jnp.where(lmask, delta, 0.0)), 0.0)

    # 1) intra-chunk: (C Bᵀ ⊙ L) X  — two MXU matmuls
    g = jnp.dot(c, b.T, preferred_element_type=jnp.float32)  # (Q, Q)
    y = jnp.dot(g * decay, x, preferred_element_type=jnp.float32)  # (Q, P)
    # 2) inter-chunk contribution of the carried state: diag(P) C h
    y += jnp.exp(acum) * jnp.dot(c, h, preferred_element_type=jnp.float32)
    # 3) state update: h <- P_Q h + Bᵀ diag(P_Q / P_s) X   (stays in VMEM)
    p_last = jnp.exp(acum[-1:, :])  # (1, 1)
    scale = jnp.exp(acum[-1:, :] - acum)  # (Q, 1) = P_Q / P_s
    h_ref[...] = p_last[0, 0] * h + jnp.dot(
        (b * scale).T, x, preferred_element_type=jnp.float32
    )

    y_ref[...] = y.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,
    a_log: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Single-head SSD scan.  x: (L, P), a_log: (L,), b/c: (L, S).

    L must be a multiple of `chunk` (the wrapper pads internally otherwise;
    padded steps use a_log = 0, b = 0 so they do not perturb the state).
    """
    L, P = x.shape
    S = b.shape[-1]
    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        a_log = jnp.pad(a_log, (0, pad))
        b = jnp.pad(b, ((0, pad), (0, 0)))
        c = jnp.pad(c, ((0, pad), (0, 0)))
    Lp = x.shape[0]
    grid = (Lp // chunk,)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, out_dtype=x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, P), lambda i: (i, 0)),
            pl.BlockSpec((chunk, 1), lambda i: (i, 0)),
            pl.BlockSpec((chunk, S), lambda i: (i, 0)),
            pl.BlockSpec((chunk, S), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, P), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Lp, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((S, P), jnp.float32)],  # the carried state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, a_log.reshape(-1, 1), b, c)
    return out[:L]
