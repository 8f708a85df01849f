"""Flash attention as an MX-pattern Pallas kernel.

The online-softmax running statistics (m, l, acc) are exactly the paper's
near-compute accumulator generalized to a normalized reduction: they persist
in VMEM scratch across the KV grid dimension, each KV tile streams through
VMEM once, and the output tile is written exactly once at the end (the
inter-k-buffering + single-write-back discipline of Table II, with K := the
KV sequence axis).

Used by the model stack when MXPolicy selects the Pallas path on TPU; the
jnp formulation (models/layers.py chunked_attention) is the sharded/XLA
equivalent and the oracle is kernels/ref.flash_attention_ref.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  nk: int, bq: int, bk: int, lq: int, lk: int, scale: float,
                  causal: bool, out_dtype):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():  # C-tile reset analogue
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)  # (bq, d)
    k = k_ref[...].astype(jnp.float32)  # (bk, d)
    v = v_ref[...].astype(jnp.float32)  # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = kpos < lk  # right padding
    if causal:
        keep &= qpos >= kpos
    s = jnp.where(keep, s, -jnp.inf)

    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(s - m_safe)  # masked lanes: exp(-inf - finite) == 0
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _store():  # single write-back of the finished output tile
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "interpret"))
def mx_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    bq: int = 128, bk: int = 128, causal: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Single-head attention. q: (Lq, d), k/v: (Lk, d) -> (Lq, d)."""
    lq, d = q.shape
    lk = k.shape[0]
    scale = 1.0 / math.sqrt(d)
    bq_, bk_ = min(bq, lq), min(bk, lk)
    pq = (-lq) % bq_
    pk = (-lk) % bk_
    if pq:
        q = jnp.pad(q, ((0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, pk), (0, 0)))
        v = jnp.pad(v, ((0, pk), (0, 0)))
    nq = q.shape[0] // bq_
    nk = k.shape[0] // bk_

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, nk=nk, bq=bq_, bk=bk_, lq=lq, lk=lk,
            scale=scale, causal=causal, out_dtype=q.dtype,
        ),
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((bq_, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk_, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bk_, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq_, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),  # m — running max
            pltpu.VMEM((bq_, 1), jnp.float32),  # l — running normalizer
            pltpu.VMEM((bq_, d), jnp.float32),  # acc — the MX tile buffer
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:lq]
