"""MX-ready Pallas matmul: the paper's technique, TPU-native — now with a
declarative fused epilogue.

The paper's near-FPU tile buffer accumulates an m'×n' output sub-tile across
the k' reduction, writing the result to the VRF once instead of
read-modify-writing it every step (inter-k-buffering, §II-C-a), and resets
instead of loading when C == 0 (§II-C-b).

TPU mapping (README §Design):
  - the output block's f32 accumulator lives in a VMEM scratch that persists
    across the innermost (k) grid dimension;
  - `@pl.when(k == 0)` zero-init  == C-tile reset (no C load);
  - `@pl.when(k == nk-1)` single write-back of the finished block == the
    single D(↑) = M*N store of Table II's MX row;
  - BlockSpec index maps are the `mld.a` / `mld.b` tile loads — the A block
    (i, k) is independent of j, so Pallas's pipeline keeps it resident while
    j advances: that is the broadcast-engine reuse of the A tile.

Epilogue fusion extends the same single-writeback argument one level up the
op graph: bias-add, residual-add, activation, and output scaling happen
*inside* the final-k store, so the GEMM result leaves VMEM exactly once —
instead of the unfused graph's matmul-store + per-elementwise-op M*N
round-trips through HBM.  The general GEMM of Eq. 1 (the C operand) is the
special case `Epilogue(residual=True)`.

Block shapes come from `core.tiling.plan_matmul_tiles` (the `msettile`
analogue).  The grid iterates (m, n, k) with k innermost ("arbitrary"
semantics — the accumulator carries a dependence), m/n parallel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .abft import AbftSpec
from .sparse import expand_24
from .vmem import compiler_params, gemm_vmem_bytes

ACTIVATIONS = ("none", "relu", "gelu", "silu", "swiglu")


def apply_activation(x: jax.Array, activation: str) -> jax.Array:
    """Elementwise activations usable both inside Pallas kernels and as the
    XLA reference path (identical primitives => comparable numerics)."""
    if activation == "none":
        return x
    if activation == "relu":
        return jax.nn.relu(x)
    if activation == "gelu":
        return jax.nn.gelu(x)
    if activation == "silu":
        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {activation!r}; one of {ACTIVATIONS}")


def dot_f32(a_blk: jax.Array, b_blk: jax.Array,
            b_transposed: bool = False) -> jax.Array:
    """One tile FMA chain with f32 accumulation for ANY operand dtype — the
    multi-precision FPU datapath (§III): narrow operands in, wide
    accumulation out.  int8×int8 takes the exact int32 MAC path (the MXU's
    int8 pipe) before widening; mixed or sub-16-bit float operands widen to
    f32 first (quantized integer VALUES are the payload — dequant scales
    are applied downstream at the write-back, never here).  Used by every
    kernel body and by the unfused XLA reference so backends accumulate
    identically.  ``b_transposed``: b_blk is (N, K), contracted on its last
    dim (the MXU's NT form — no transposed copy is made).

    An f32 operand is multiplied at f32 precision (``HIGHEST``): the TPU's
    default contracts f32 in bf16 passes, which would round every f32
    weight to 8 mantissa bits.  Operands widened from bf16 or narrower
    are exact in one pass, so they keep the default."""
    dims = (((a_blk.ndim - 1,), (1 if b_transposed else 0,)), ((), ()))
    if a_blk.dtype == b_blk.dtype and jnp.issubdtype(a_blk.dtype, jnp.integer):
        return jax.lax.dot_general(
            a_blk, b_blk, dims, preferred_element_type=jnp.int32
        ).astype(jnp.float32)
    f32_in = jnp.float32 in (a_blk.dtype, b_blk.dtype)
    if a_blk.dtype != b_blk.dtype or a_blk.dtype.itemsize < 2:
        a_blk = a_blk.astype(jnp.float32)
        b_blk = b_blk.astype(jnp.float32)
    return jax.lax.dot_general(
        a_blk, b_blk, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if f32_in else None)


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Declarative spec of what happens to the output tile at the final-k
    write-back, while it is still resident in VMEM.

    Semantics (in application order, all in f32):
        acc *= a_scale * b_scale           [dequant: quantized operands]
        acc += bias                        [bias]
        acc  = act(acc)  or  silu(gate_acc * a_scale * bg_scale) * acc  [swiglu]
        acc += residual                    [residual]
        acc *= out_scale                   [out_scale]
        out  = acc.astype(out_dtype)       (the ONE write-back)

    ``swiglu`` pairs the main GEMM with a second GEMM against a gate weight
    (same shape as B) accumulated in a second VMEM scratch; the gating
    multiply happens at the write-back, so the intermediate up/gate
    projections never exist in HBM at all.

    ``a_scale`` / ``b_scale`` declare quantized-operand dequant scales
    (core/precision.py): the kernel loads narrow A/B payloads and applies
    the per-row (M, 1) / per-column (1, N) f32 scales to the finished
    accumulator at the same single write-back — scales are constant along
    K, so the inter-k accumulator is touched only by FMAs, exactly as in
    the unquantized kernel.  The gate GEMM reuses a_scale and takes its own
    ``bg_scale`` for the (independently quantized) gate weight.
    """

    activation: str = "none"
    bias: bool = False
    residual: bool = False
    out_scale: Optional[float] = None
    a_scale: bool = False
    b_scale: bool = False

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise ValueError(
                f"unknown activation {self.activation!r}; one of {ACTIVATIONS}"
            )

    @property
    def has_gate(self) -> bool:
        return self.activation == "swiglu"

    @property
    def n_fused_ops(self) -> int:
        """How many elementwise HBM round-trips the fusion eliminates
        (consumed by core.transfer_model's epilogue accounting)."""
        n = 0
        if self.a_scale:
            n += 1  # unfused graph: one M*N dequant multiply on the output
        if self.b_scale:
            n += 1
        if self.bias:
            n += 1
        if self.activation == "swiglu":
            n += 2  # silu(gate) and the gating multiply
        elif self.activation != "none":
            n += 1
        if self.residual:
            n += 1
        if self.out_scale is not None:
            n += 1
        return n


def apply_epilogue(
    y: jax.Array,
    epilogue: Epilogue,
    *,
    bias: Optional[jax.Array] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    a_scale: Optional[jax.Array] = None,
    b_scale: Optional[jax.Array] = None,
    bg_scale: Optional[jax.Array] = None,
    out_dtype=None,
) -> jax.Array:
    """Unfused reference application of an Epilogue to a f32 GEMM result,
    in EXACTLY the order the fused kernel's final-k write-back uses:
    dequant scales -> bias -> activation/gating -> residual -> out_scale.
    Every unfused path (xla dispatch, ring collective final steps,
    serialized references) must go through this one helper so epilogue
    semantics cannot silently diverge from the kernel.  ``gate`` is the
    gate GEMM's f32 result (quantized VALUES, not yet dequantized) when
    ``epilogue.has_gate``; ``a_scale`` (M, 1) / ``b_scale`` (1, N) /
    ``bg_scale`` (1, N) are the operand dequant scales."""
    if epilogue.has_gate != (gate is not None):
        raise ValueError("gate must be given iff epilogue.activation=='swiglu'")
    if epilogue.a_scale != (a_scale is not None):
        raise ValueError("a_scale operand must match epilogue.a_scale")
    if epilogue.b_scale != (b_scale is not None):
        raise ValueError("b_scale operand must match epilogue.b_scale")
    if (bg_scale is not None) != (epilogue.has_gate and epilogue.b_scale):
        raise ValueError("bg_scale must be given iff the epilogue is gated "
                         "AND b_scale is set (the gate weight quantizes "
                         "independently of the up weight)")
    if a_scale is not None:
        y = y * a_scale
        if gate is not None:
            gate = gate * a_scale
    if b_scale is not None:
        y = y * b_scale
    if gate is not None and bg_scale is not None:
        gate = gate * bg_scale
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if epilogue.has_gate:
        y = jax.nn.silu(gate) * y
    else:
        y = apply_activation(y, epilogue.activation)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if epilogue.out_scale is not None:
        y = y * jnp.float32(epilogue.out_scale)
    return y if out_dtype is None else y.astype(out_dtype)


def abft_accumulate(abft: AbftSpec, a_blk, b_blk, ccol_ref, crow_ref,
                    acol_ref, arow_ref) -> None:
    """One k-step of checksum accumulation: the extra row/column of the
    checksum-extended GEMM, summed FIRST and multiplied second, so their
    rounding (and any corruption of the main FMA stream) is independent of
    the main accumulator.  Shared by the plain and grouped kernel bodies."""
    cdt = jnp.int32 if abft.exact else jnp.float32
    a_c = a_blk.astype(cdt)
    b_c = b_blk.astype(cdt)
    ccol_ref[...] += jnp.dot(jnp.sum(a_c, axis=0, keepdims=True), b_c,
                             preferred_element_type=cdt)
    crow_ref[...] += jnp.dot(a_c, jnp.sum(b_c, axis=1, keepdims=True),
                             preferred_element_type=cdt)
    if acol_ref is not None:
        # |a|/|b| checksums: the scale of legitimate rounding error,
        # against which the tolerance compare is taken.
        a_a = jnp.abs(a_c)
        b_a = jnp.abs(b_c)
        acol_ref[...] += jnp.dot(jnp.sum(a_a, axis=0, keepdims=True), b_a,
                                 preferred_element_type=jnp.float32)
        arow_ref[...] += jnp.dot(a_a, jnp.sum(b_a, axis=1, keepdims=True),
                                 preferred_element_type=jnp.float32)


def abft_inject(acc, fd_ref, fr_ref, fc_ref):
    """Apply the (1, 1)-blocked fault operands to the finished accumulator:
    additive delta at one (row, col).  The where() keeps every untargeted
    element — and the whole tile when delta == 0 — bitwise untouched."""
    delta = fd_ref[0, 0]
    rows = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    hit = ((rows == fr_ref[0, 0]) & (cols == fc_ref[0, 0])
           & (delta != 0.0))
    return jnp.where(hit, acc + delta, acc)


def abft_verify(abft: AbftSpec, acc, ccol_ref, crow_ref, acol_ref, arow_ref):
    """Compare the finished accumulator's row/column sums against the
    checksums; returns the int32 tile flag (1 = corrupt).  Integer payloads
    compare exactly; floats against rtol * |.|-checksum + atol."""
    if abft.exact:
        ai = acc.astype(jnp.int32)
        col_bad = jnp.any(jnp.sum(ai, axis=0, keepdims=True) != ccol_ref[...])
        row_bad = jnp.any(jnp.sum(ai, axis=1, keepdims=True) != crow_ref[...])
    else:
        dcol = jnp.abs(jnp.sum(acc, axis=0, keepdims=True) - ccol_ref[...])
        drow = jnp.abs(jnp.sum(acc, axis=1, keepdims=True) - crow_ref[...])
        rtol = jnp.float32(abft.rtol)
        atol = jnp.float32(abft.atol)
        col_bad = jnp.any(dcol > rtol * acol_ref[...] + atol)
        row_bad = jnp.any(drow > rtol * arow_ref[...] + atol)
    return (col_bad | row_bad).astype(jnp.int32)


def abft_scratch(abft: Optional[AbftSpec], bm: int, bn: int) -> list:
    """Checksum scratch buffers for one kernel launch, in the consumption
    order of the kernel bodies: ccol, crow, [acol, arow]."""
    if abft is None:
        return []
    cdt = jnp.int32 if abft.exact else jnp.float32
    shapes = [pltpu.VMEM((1, bn), cdt), pltpu.VMEM((bm, 1), cdt)]
    if not abft.exact:
        shapes += [pltpu.VMEM((1, bn), jnp.float32),
                   pltpu.VMEM((bm, 1), jnp.float32)]
    return shapes


def _fused_kernel(*refs, nk: int, out_dtype, epilogue: Epilogue,
                  abft: Optional[AbftSpec] = None, b_sparse: bool = False,
                  b_transposed: bool = False):
    """Kernel body.  refs layout (inputs, outputs, scratch):
    a, b, [b_meta], [b_gate], [bg_meta], [a_scale], [b_scale], [bg_scale],
    [bias], [residual], [fault_delta, fault_row, fault_col],
    o, [flags], acc, [acc_gate], [ccol, crow, [acol, arow]].

    With ``b_sparse`` the b / b_gate refs hold the 2:4 COMPRESSED payload
    blocks (bk/2, bn) and b_meta / bg_meta the packed index blocks
    (bk/8, bn); `expand_24` rebuilds the dense (bk, bn) tile in VMEM right
    before the dot — the metadata streams with the k step exactly like the
    dequant scale slots stream with j."""
    it = iter(refs)
    a_ref = next(it)
    b_ref = next(it)
    bmeta_ref = next(it) if b_sparse else None
    bg_ref = next(it) if epilogue.has_gate else None
    bgmeta_ref = next(it) if (epilogue.has_gate and b_sparse) else None
    as_ref = next(it) if epilogue.a_scale else None
    bs_ref = next(it) if epilogue.b_scale else None
    bgs_ref = next(it) if (epilogue.has_gate and epilogue.b_scale) else None
    bias_ref = next(it) if epilogue.bias else None
    res_ref = next(it) if epilogue.residual else None
    inject = abft is not None and abft.inject
    fd_ref = next(it) if inject else None
    fr_ref = next(it) if inject else None
    fc_ref = next(it) if inject else None
    o_ref = next(it)
    flags_ref = next(it) if abft is not None else None
    acc_ref = next(it)
    accg_ref = next(it) if epilogue.has_gate else None
    ccol_ref = next(it) if abft is not None else None
    crow_ref = next(it) if abft is not None else None
    acol_ref = next(it) if (abft is not None and not abft.exact) else None
    arow_ref = next(it) if (abft is not None and not abft.exact) else None

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():  # C-tile reset: initialize the near-compute accumulator(s)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if accg_ref is not None:
            accg_ref[...] = jnp.zeros_like(accg_ref)
        if ccol_ref is not None:
            ccol_ref[...] = jnp.zeros_like(ccol_ref)
            crow_ref[...] = jnp.zeros_like(crow_ref)
        if acol_ref is not None:
            acol_ref[...] = jnp.zeros_like(acol_ref)
            arow_ref[...] = jnp.zeros_like(arow_ref)

    # mxfmacc: one systolic-tile FMA chain into the resident accumulator —
    # narrow (int8/fp8) payloads take the multi-precision datapath of
    # dot_f32; the accumulator is f32 regardless of operand width.  Sparse
    # payloads expand in VMEM first (compare-selects, no gathers), so HBM
    # only ever saw the compressed stream.
    a_blk = a_ref[...]
    b_blk = (expand_24(b_ref[...], bmeta_ref[...]) if b_sparse
             else b_ref[...])
    acc_ref[...] += dot_f32(a_blk, b_blk, b_transposed)
    if accg_ref is not None:
        bg_blk = (expand_24(bg_ref[...], bgmeta_ref[...]) if b_sparse
                  else bg_ref[...])
        accg_ref[...] += dot_f32(a_blk, bg_blk)

    if ccol_ref is not None:
        abft_accumulate(abft, a_blk, b_blk, ccol_ref, crow_ref,
                        acol_ref, arow_ref)

    @pl.when(k == nk - 1)
    def _store():  # single write-back, with the epilogue applied in VMEM
        acc = acc_ref[...]
        if inject:
            # Injected SDC lands on the finished accumulator AFTER the
            # checksums closed over the true products and BEFORE the
            # verify — exactly where a write-back bit flip would strike.
            acc = abft_inject(acc, fd_ref, fr_ref, fc_ref)
        if flags_ref is not None:
            flags_ref[0, 0] = abft_verify(abft, acc, ccol_ref, crow_ref,
                                          acol_ref, arow_ref)
        # dequant first: scales are constant along K, so applying them to
        # the finished accumulator == applying them per-FMA, at 1/nk cost.
        if as_ref is not None:
            acc = acc * as_ref[...]
        if bs_ref is not None:
            acc = acc * bs_ref[...]
        if bias_ref is not None:
            acc = acc + bias_ref[...].astype(jnp.float32)
        if epilogue.has_gate:
            gate = accg_ref[...]
            if as_ref is not None:
                gate = gate * as_ref[...]
            if bgs_ref is not None:
                gate = gate * bgs_ref[...]
            acc = jax.nn.silu(gate) * acc
        else:
            acc = apply_activation(acc, epilogue.activation)
        if res_ref is not None:
            acc = acc + res_ref[...].astype(jnp.float32)
        if epilogue.out_scale is not None:
            acc = acc * jnp.float32(epilogue.out_scale)
        o_ref[...] = acc.astype(out_dtype)


def _pad_to(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "bm", "bn", "bk", "out_dtype", "interpret",
                     "abft", "b_sparse", "b_transposed"),
)
def mx_matmul_fused(
    a: jax.Array,
    b: jax.Array,
    *,
    epilogue: Epilogue = Epilogue(),
    b_gate: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    a_scale: Optional[jax.Array] = None,
    b_scale: Optional[jax.Array] = None,
    bg_scale: Optional[jax.Array] = None,
    b_sparse: bool = False,
    b_meta: Optional[jax.Array] = None,
    bg_meta: Optional[jax.Array] = None,
    b_transposed: bool = False,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
    abft: Optional[AbftSpec] = None,
    fault_delta: Optional[jax.Array] = None,
    fault_row: Optional[jax.Array] = None,
    fault_col: Optional[jax.Array] = None,
):
    """D = epilogue(A @ B), with the epilogue fused into the single final-k
    write-back.  a: (M, K), b: (K, N); bias: (N,); residual: (M, N);
    b_gate: (K, N) when epilogue.activation == "swiglu".

    Quantized operands: a/b/b_gate carry narrow payloads (int8/fp8 — the
    quantized VALUES), with per-row ``a_scale`` (M, 1) and per-column
    ``b_scale`` / ``bg_scale`` (1, N) f32 dequant scales applied at the
    write-back (see kernels/quant.quantize_operand; per-tensor scales are
    pre-broadcast to the same layout).  out_dtype defaults to a.dtype —
    always pass it explicitly for quantized payloads.

    ABFT: with ``abft`` set (kernels/abft.AbftSpec), the kernel carries
    checksum accumulators alongside the tile accumulator, verifies the
    finished tile inside the same final-k write-back, and returns
    ``(out, flags)`` where flags is the (grid_m, grid_n) int32 per-tile
    corruption map (0 = verified clean).  The main accumulator datapath is
    untouched, so the ``out`` payload is bitwise identical to ``abft=None``.
    ``fault_*`` are the optional (grid_m, grid_n) injection operands built
    by abft.build_fault_operands (present iff ``abft.inject``).

    2:4 sparsity: with ``b_sparse`` the b / b_gate operands carry the
    COMPRESSED payload (K/2, N) and ``b_meta`` / ``bg_meta`` the packed
    uint8 indices (K/8, N) (kernels/sparse.compress_24).  K and bk must be
    multiples of 8 so payload and metadata tile evenly; the kernel expands
    each staged block in VMEM before the dot, so HBM traffic is the
    compressed stream.  Composes with ``b_scale`` quantization (payload
    holds quantized values; per-column scales are constant along K, so
    pruning does not disturb them) but not with ``abft`` (checksum
    recovery needs dense weight slices — callers decompress first).

    ``b_transposed``: b is stored (N, K) — a tied embedding table serving as
    the LM head — and each (bn, bk) block is contracted on its last dim by
    the MXU's NT matmul, so no transposed copy of the table is ever made.
    Plain epilogues only (no gate, sparsity or ABFT).
    """
    if b_transposed and (epilogue.has_gate or b_sparse or abft is not None
                         or epilogue.b_scale):
        raise ValueError("b_transposed supports plain (ungated, dense, "
                         "unscaled, unchecked) GEMMs only")
    b_stored = b
    if b_transposed:
        b = b.T  # logical (K, N) view for the shape checks below
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"mx_matmul expects 2-D operands, got {a.shape}, {b.shape}")
    if epilogue.has_gate != (b_gate is not None):
        raise ValueError("b_gate must be given iff epilogue.activation=='swiglu'")
    if epilogue.bias != (bias is not None):
        raise ValueError("bias operand must match epilogue.bias")
    if epilogue.residual != (residual is not None):
        raise ValueError("residual operand must match epilogue.residual")
    if epilogue.a_scale != (a_scale is not None):
        raise ValueError("a_scale operand must match epilogue.a_scale")
    if epilogue.b_scale != (b_scale is not None):
        raise ValueError("b_scale operand must match epilogue.b_scale")
    if (bg_scale is not None) != (epilogue.has_gate and epilogue.b_scale):
        raise ValueError("bg_scale must be given iff the epilogue is gated "
                         "AND b_scale is set")
    inject = abft is not None and abft.inject
    if inject != (fault_delta is not None):
        raise ValueError("fault operands must be given iff abft.inject")
    if b_sparse != (b_meta is not None):
        raise ValueError("b_meta must be given iff b_sparse")
    if (bg_meta is not None) != (b_sparse and epilogue.has_gate):
        raise ValueError("bg_meta must be given iff b_sparse AND the "
                         "epilogue is gated")
    if b_sparse and abft is not None:
        raise ValueError("b_sparse does not compose with abft in-kernel; "
                         "decompress to dense for the checksummed path")
    M, K = a.shape
    if b_sparse:
        K2, N = b.shape  # compressed payload: K2 == K/2
        if 2 * K2 != K:
            raise ValueError(f"sparse payload K/2={K2} inconsistent with "
                             f"a's K={K}")
        if K % 8 != 0:
            raise ValueError(f"2:4 sparse GEMM needs K % 8 == 0, got {K}")
        if b_meta.shape != (K // 8, N) or b_meta.dtype != jnp.uint8:
            raise ValueError(f"b_meta must be uint8 ({K // 8}, {N}), got "
                             f"{b_meta.dtype} {b_meta.shape}")
    else:
        K2, N = b.shape
        assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype

    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    if b_sparse and bk_ % 8 != 0:
        raise ValueError(f"2:4 sparse GEMM needs bk % 8 == 0, got {bk_}")
    a_p = _pad_to(a, bm_, bk_)
    # N is padded only for ABFT, whose checksums sum every column of a
    # tile.  Otherwise the last column block may run past N: its stray
    # columns never reach the output (the write-back is masked), so a
    # (128256, d) tied embedding table is read in place instead of being
    # copied into a padded buffer on every call.
    bn_pad = bn_ if abft is not None else 1
    # Sparse payload/metadata pad in their own compressed units: K % 8 == 0
    # and bk % 8 == 0 make the K-pad a multiple of 8, so the padded payload
    # stays exactly Kp/2 rows (and metadata Kp/8) — zero payload expands to
    # a zero dense block, so the degenerate padded metadata is harmless.
    if b_transposed:  # mld.b of the stored (N, K) table, block (bn, bk)
        b_p = _pad_to(b_stored, bn_pad, bk_)
        Np = b_p.shape[0]
        b_spec = pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k))
    else:  # mld.b (payload when sparse)
        b_p = (_pad_to(b, bk_ // 2, bn_pad) if b_sparse
               else _pad_to(b, bk_, bn_pad))
        Np = b_p.shape[1]
        b_spec = pl.BlockSpec((bk_ // 2 if b_sparse else bk_, bn_),
                              lambda i, j, k: (k, j))
    Mp, Kp = a_p.shape
    nk = Kp // bk_
    grid = (Mp // bm_, pl.cdiv(Np, bn_), nk)
    in_specs = [
        pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),  # mld.a
        b_spec,
    ]
    operands = [a_p, b_p]
    scratch = [pltpu.VMEM((bm_, bn_), jnp.float32)]  # the tile buffer
    if b_sparse:
        # packed 2-bit indices ride the same (k, j) steering as the payload
        in_specs.append(pl.BlockSpec((bk_ // 8, bn_), lambda i, j, k: (k, j)))
        operands.append(_pad_to(b_meta, bk_ // 8, bn_pad))
    if epilogue.has_gate:
        in_specs.append(pl.BlockSpec((bk_ // 2 if b_sparse else bk_, bn_),
                                     lambda i, j, k: (k, j)))
        operands.append(_pad_to(b_gate, bk_ // 2 if b_sparse else bk_,
                                bn_pad))
        if b_sparse:
            in_specs.append(
                pl.BlockSpec((bk_ // 8, bn_), lambda i, j, k: (k, j)))
            operands.append(_pad_to(bg_meta, bk_ // 8, bn_pad))
        scratch.append(pltpu.VMEM((bm_, bn_), jnp.float32))
    if epilogue.a_scale:
        # (M, 1) per-row scale panel rides with the i tile (padded rows of
        # A are zero, so their scale value is irrelevant).
        in_specs.append(pl.BlockSpec((bm_, 1), lambda i, j, k: (i, 0)))
        operands.append(_pad_to(a_scale.astype(jnp.float32), bm_, 1))
    if epilogue.b_scale:
        in_specs.append(pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)))
        operands.append(_pad_to(b_scale.astype(jnp.float32), 1, bn_pad))
        if epilogue.has_gate:
            in_specs.append(pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)))
            operands.append(_pad_to(bg_scale.astype(jnp.float32), 1,
                                    bn_pad))
    if epilogue.bias:
        # (N,) -> (1, N): the bias block rides along with the (i, j) tile and
        # is consumed only at the final-k store.
        in_specs.append(pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)))
        operands.append(_pad_to(bias.reshape(1, -1), 1, bn_pad))
    if epilogue.residual:
        in_specs.append(pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)))
        operands.append(_pad_to(residual, bm_, bn_pad))
    grid_m, grid_n = grid[0], grid[1]
    if inject:
        for arr, dt in ((fault_delta, jnp.float32), (fault_row, jnp.int32),
                        (fault_col, jnp.int32)):
            if arr.shape != (grid_m, grid_n):
                raise ValueError(f"fault operand shape {arr.shape} != grid "
                                 f"({grid_m}, {grid_n})")
            in_specs.append(pl.BlockSpec((1, 1), lambda i, j, k: (i, j)))
            operands.append(arr.astype(dt))

    out_specs = pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j))  # mst.c
    out_shape = jax.ShapeDtypeStruct((Mp, Np), out_dtype)
    if abft is not None:
        out_specs = (out_specs,
                     pl.BlockSpec((1, 1), lambda i, j, k: (i, j)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((grid_m, grid_n), jnp.int32))
        scratch.extend(abft_scratch(abft, bm_, bn_))

    kernel = functools.partial(
        _fused_kernel, nk=nk, out_dtype=out_dtype, epilogue=epilogue,
        abft=abft, b_sparse=b_sparse, b_transposed=b_transposed,
    )
    vmem = gemm_vmem_bytes(
        bm_, bn_, bk_, a_bytes=a.dtype.itemsize, b_bytes=b.dtype.itemsize,
        out_bytes=jnp.dtype(out_dtype).itemsize, gated=epilogue.has_gate,
        b_sparse=b_sparse, a_scale=epilogue.a_scale,
        b_scale=epilogue.b_scale, bias=epilogue.bias,
        residual_bytes=residual.dtype.itemsize if epilogue.residual else 0)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem),
        interpret=interpret,
        name="mx_matmul_fused",
    )(*operands)
    if abft is not None:
        out, flags = out
        return out[:M, :N], flags
    return out[:M, :N]


def mx_matmul(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """D = A @ B (+ C), MX-style: f32 VMEM accumulator across the K grid.

    The general GEMM's C operand (Eq. 1) is the `residual` epilogue: with no
    activation, adding C at the final write-back equals loading it into the
    accumulator at k == 0 (both happen in f32), and keeps one kernel body.
    """
    ep = Epilogue(residual=c is not None)
    return mx_matmul_fused(
        a, b, epilogue=ep, residual=c,
        bm=bm, bn=bn, bk=bk, out_dtype=out_dtype, interpret=interpret,
    )
