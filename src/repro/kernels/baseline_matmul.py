"""Baseline (non-MX) Pallas matmul: the paper's vector-baseline traffic pattern.

No inter-k buffering: the output block is *read-modify-written through the
output ref on every k step*, so partial sums round-trip one level up the
hierarchy K/bk times — exactly the (K/k)·M·N down + (K/k)·M·N up terms of
Table I ref. 1) that MX eliminates.  Accumulation happens in the output
dtype (the VRF holds architectural-width elements), which for narrow dtypes
also exposes the precision cost of not having the f32 near-FPU buffer.

This kernel exists so benchmarks can compare MX vs baseline on identical
block shapes, isolating the accumulator-placement effect (the paper's Fig. 3
comparison), and so the traffic delta predicted by `core.transfer_model`
can be checked against the HLO/interpret traffic of both kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mx_matmul import _pad_to
from .vmem import compiler_params, gemm_vmem_bytes


def _baseline_kernel(a_ref, b_ref, o_ref, *, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():  # C-tile reset still applies (C == 0)
        o_ref[...] = jnp.zeros_like(o_ref)

    # Partial sum accumulated *in the output block itself* — it round-trips
    # between VMEM and HBM on every k step (Pallas re-fetches and re-writes
    # the (i, j) output block each time the grid revisits it).
    part = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = (o_ref[...].astype(jnp.float32) + part).astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def baseline_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"baseline_matmul expects 2-D operands, got {a.shape}, {b.shape}")
    M, K = a.shape
    _, N = b.shape
    out_dtype = out_dtype or a.dtype

    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    a_p = _pad_to(a, bm_, bk_)
    b_p = _pad_to(b, bk_, bn_)
    Mp, Kp = a_p.shape
    Np = b_p.shape[1]
    grid = (Mp // bm_, Np // bn_, Kp // bk_)

    out = pl.pallas_call(
        functools.partial(_baseline_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"),
            gemm_vmem_bytes(bm_, bn_, bk_, a_bytes=a.dtype.itemsize,
                            b_bytes=b.dtype.itemsize,
                            out_bytes=jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
        name="baseline_matmul",
    )(a_p, b_p)
    return out[:M, :N]
