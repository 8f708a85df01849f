"""Grouped (ragged) MX matmul: all experts of an MoE layer in ONE kernel.

Problem: out[t] = x[t] @ w[g(t)]  where rows of x are sorted by group
(expert) and group g owns `group_sizes[g]` contiguous rows.  A Python loop
of per-expert matmuls launches E kernels and re-reads the activations; this
kernel walks every (group, row-tile) pair in a single Pallas launch.

Ragged sizes are handled with *group-offset scalar prefetch* (the
megablocks/ragged-dot construction): the wrapper computes, per logical grid
step, which group and which global row-tile it works on, and ships those
maps to SMEM via `pltpu.PrefetchScalarGridSpec` so the BlockSpec index maps
can steer the A/W/out DMAs before the kernel body runs.  A row-tile that
straddles a group boundary is visited once per group with complementary row
masks — the two visits are consecutive in the grid, so the output block
stays resident in VMEM between them (no extra HBM round-trip).

The MX structure is unchanged from mx_matmul: f32 VMEM accumulator across
the innermost k axis, `@pl.when(k == 0)` reset, single masked write-back at
k == nk-1 — with an optional fused activation epilogue applied in VMEM.

Grid: (n_tiles, logical_row_tiles, k_tiles); the logical axis has static
length  ceil(Tp/bm) + G  (every group can add at most one straddled tile).
Unused trailing slots replay the last real (group, tile) pair — idempotent,
since they store the same masked result again.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .abft import AbftSpec
from .mx_matmul import (abft_accumulate, abft_inject, abft_scratch,
                        abft_verify, apply_activation, dot_f32)
from .sparse import expand_24
from .vmem import compiler_params, gemm_vmem_bytes


def make_group_metadata(
    group_sizes: jax.Array, bm: int, num_slots: int, n_tiles: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-logical-slot steering arrays (all shape (num_slots,) except the
    per-group starts): slot -> (group id, global row-tile id, first-writer
    flag).  Computed with jnp so traced (data-dependent) group sizes work;
    the results ride to SMEM as scalar-prefetch operands.

    Trailing dummy slots are steered at the row-tiles *no* group owns
    (everything past sum(sizes)): their row mask is empty, so with the
    first-writer flag set the kernel zero-fills those tiles in the same
    launch — no post-kernel masking pass over the output.  Dummies left
    over after that sweep pin to the last tile with first=0 (a no-op
    rewrite of the still-resident block).

    Group ranges are clamped to the padded row count (`n_tiles * bm`):
    oversubscribed group_sizes (sum > T, a caller arithmetic bug) degrade
    to dropping the nonexistent rows instead of steering the BlockSpec
    index maps to out-of-bounds tiles (a silent OOB DMA on real TPU).
    """
    t_padded = n_tiles * bm
    sizes_raw = group_sizes.astype(jnp.int32)
    ends_raw = jnp.cumsum(sizes_raw)
    starts = jnp.minimum(ends_raw - sizes_raw, t_padded)
    ends = jnp.minimum(ends_raw, t_padded)
    sizes = ends - starts
    nonempty = sizes > 0
    t0 = jnp.where(nonempty, starts // bm, 0)
    t1 = jnp.where(nonempty, (ends - 1) // bm, -1)
    ng = jnp.where(nonempty, t1 - t0 + 1, 0)  # row-tiles visited per group
    slot_start = jnp.cumsum(ng) - ng
    total_slots = jnp.sum(ng)

    slots = jnp.arange(num_slots, dtype=jnp.int32)
    # Which group does slot i belong to?  searchsorted over the cumulative
    # slot counts skips empty groups (their cumsum is flat).
    grp = jnp.searchsorted(jnp.cumsum(ng), slots, side="right").astype(jnp.int32)
    is_real = slots < total_slots
    grp = jnp.where(is_real, grp, 0)
    tile = t0[grp] + (slots - slot_start[grp])
    # Dummy slots sweep the uncovered tail tiles (zero-fill), then pin to
    # the last tile.  An uncovered tile's rows are >= sum(sizes), so any
    # group id gives an all-false row mask there; grp 0 is as good as any.
    total_rows = jnp.sum(sizes)
    covered_end = jnp.where(total_rows > 0, (total_rows - 1) // bm, -1)
    raw = covered_end + 1 + (slots - total_slots)
    tile_dummy = jnp.clip(raw, 0, max(n_tiles - 1, 0))
    zero_fill = (~is_real) & (raw < n_tiles)
    tile = jnp.where(is_real, tile, tile_dummy).astype(jnp.int32)
    # First-writer flag: first real slot of a tile, or a zero-fill dummy.
    prev_tile = jnp.concatenate([jnp.array([-1], jnp.int32), tile[:-1]])
    first = ((is_real & (tile != prev_tile)) | zero_fill).astype(jnp.int32)
    return grp, tile, first, starts.astype(jnp.int32), sizes


def _grouped_kernel(
    # scalar-prefetch refs (SMEM):
    grp_ref, tile_ref, first_ref, starts_ref, sizes_ref,
    # tensor refs:
    *refs,
    nk: int,
    bm: int,
    out_dtype,
    activation: str,
    has_gate: bool,
    has_a_scale: bool = False,
    has_b_scale: bool = False,
    abft: Optional[AbftSpec] = None,
    b_sparse: bool = False,
):
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it)
    wmeta_ref = next(it) if b_sparse else None
    wg_ref = next(it) if has_gate else None
    wgmeta_ref = next(it) if (has_gate and b_sparse) else None
    as_ref = next(it) if has_a_scale else None
    bs_ref = next(it) if has_b_scale else None
    bgs_ref = next(it) if (has_gate and has_b_scale) else None
    inject = abft is not None and abft.inject
    fd_ref = next(it) if inject else None
    fr_ref = next(it) if inject else None
    fc_ref = next(it) if inject else None
    o_ref = next(it)
    flags_ref = next(it) if abft is not None else None
    acc_ref = next(it)
    accg_ref = next(it) if has_gate else None
    ccol_ref = next(it) if abft is not None else None
    crow_ref = next(it) if abft is not None else None
    acol_ref = next(it) if (abft is not None and not abft.exact) else None
    arow_ref = next(it) if (abft is not None and not abft.exact) else None

    l = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if accg_ref is not None:
            accg_ref[...] = jnp.zeros_like(accg_ref)
        if ccol_ref is not None:
            ccol_ref[...] = jnp.zeros_like(ccol_ref)
            crow_ref[...] = jnp.zeros_like(crow_ref)
        if acol_ref is not None:
            acol_ref[...] = jnp.zeros_like(acol_ref)
            arow_ref[...] = jnp.zeros_like(arow_ref)

    x_blk = x_ref[...]
    # Sparse experts: the staged block is THIS group's compressed payload
    # (steered by grp[l], like the scale slots); expand in VMEM, then the
    # identical FMA chain.
    w_blk = (expand_24(w_ref[0], wmeta_ref[0]) if b_sparse else w_ref[0])
    acc_ref[...] += dot_f32(x_blk, w_blk)
    if accg_ref is not None:
        wg_blk = (expand_24(wg_ref[0], wgmeta_ref[0]) if b_sparse
                  else wg_ref[0])
        accg_ref[...] += dot_f32(x_blk, wg_blk)

    if ccol_ref is not None:
        # Per-expert checksums: w_ref is already THIS slot's group weight
        # block (steered by grp[l]), so the same accumulate helper covers
        # the ragged case with zero extra steering logic.
        abft_accumulate(abft, x_blk, w_blk, ccol_ref, crow_ref,
                        acol_ref, arow_ref)

    @pl.when(k == nk - 1)
    def _store():
        g = grp_ref[l]
        t = tile_ref[l]
        rows = t * bm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        start = starts_ref[g]
        valid = (rows >= start) & (rows < start + sizes_ref[g])
        acc = acc_ref[...]
        if inject:
            acc = abft_inject(acc, fd_ref, fr_ref, fc_ref)
        if flags_ref is not None:
            # A straddled row-tile is finished by consecutive slots (one per
            # group); each visit verifies ITS full accumulator, and the
            # flags merge exactly like the output block: the first writer
            # resets, later writers OR into the still-resident flag — so a
            # corruption caught by the first visit survives the second.
            flag = abft_verify(abft, acc, ccol_ref, crow_ref,
                               acol_ref, arow_ref)
            prev_flag = jnp.where(first_ref[l] == 1, 0, flags_ref[0, 0])
            flags_ref[0, 0] = prev_flag | flag
        # dequant at the single write-back: per-row activation scales and
        # THIS group's per-column weight scales (steered by grp[l], exactly
        # like the weight blocks themselves).
        if as_ref is not None:
            acc = acc * as_ref[...]
        if bs_ref is not None:
            acc = acc * bs_ref[0]
        if accg_ref is not None:
            gate = accg_ref[...]
            if as_ref is not None:
                gate = gate * as_ref[...]
            if bgs_ref is not None:
                gate = gate * bgs_ref[0]
            acc = jax.nn.silu(gate) * acc
        else:
            acc = apply_activation(acc, activation)
        acc = acc.astype(out_dtype)
        # A straddled row-tile is finished by consecutive slots: the first
        # writer zero-fills its complement, later writers merge with the
        # still-resident block (= the paper's single write-back per tile;
        # the merge never leaves VMEM).
        prev = jnp.where(first_ref[l] == 1, jnp.zeros_like(acc), o_ref[...])
        o_ref[...] = jnp.where(valid, acc, prev)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "bm", "bn", "bk", "out_dtype", "interpret",
                     "abft", "b_sparse"),
)
def mx_grouped_matmul(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    *,
    w_gate: Optional[jax.Array] = None,
    activation: str = "none",
    a_scale: Optional[jax.Array] = None,
    b_scale: Optional[jax.Array] = None,
    bg_scale: Optional[jax.Array] = None,
    b_sparse: bool = False,
    w_meta: Optional[jax.Array] = None,
    wg_meta: Optional[jax.Array] = None,
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
    """out[t] = act(x[t] @ w[g(t)]):  x: (T, K) rows sorted by group,
    w: (G, K, N), group_sizes: (G,) ints with sum <= T.  Rows beyond
    sum(group_sizes) are zero in the output.  activation == "swiglu" gates
    with a second weight set `w_gate` (G, K, N), fused in VMEM.

    Quantized operands carry narrow payloads plus dequant scales applied at
    the masked single write-back: ``a_scale`` (T, 1) per token row,
    ``b_scale`` / ``bg_scale`` (G, 1, N) PER EXPERT per output column —
    the scale blocks are steered by the same group-offset scalar-prefetch
    maps (grp[l]) that steer the expert weight blocks, so per-expert
    dequant costs no extra launches or gathers.

    ABFT: with ``abft`` set the kernel carries per-expert checksums (the
    weight block is already steered by grp[l], so the checksum sees exactly
    the expert the accumulator saw) and returns ``(out, flags)`` with flags
    shaped (row_tiles, col_tiles) int32.  Straddled tiles OR the per-group
    visit verdicts.  ``fault_*`` are the optional (row_tiles, col_tiles)
    injection operands (present iff ``abft.inject``).

    2:4 sparse experts: with ``b_sparse`` the w / w_gate operands carry the
    per-expert COMPRESSED payloads (G, K/2, N) and ``w_meta`` / ``wg_meta``
    the packed uint8 indices (G, K/8, N); the grp[l] scalar-prefetch maps
    steer both exactly like the per-expert scale blocks, and each staged
    block expands in VMEM before the dot.  Needs K % 8 == 0 and
    bk % 8 == 0; does not compose with ``abft`` in-kernel.
    """
    if x.ndim != 2 or w.ndim != 3:
        raise ValueError(f"expected x (T, K), w (G, K, N); got {x.shape}, {w.shape}")
    T, K = x.shape
    if b_sparse:
        if w_meta is None:
            raise ValueError("w_meta must be given iff b_sparse")
        if abft is not None:
            raise ValueError("b_sparse does not compose with abft in-kernel; "
                             "decompress to dense for the checksummed path")
        G, K2, N = w.shape  # compressed payload: K2 == K/2
        if 2 * K2 != K:
            raise ValueError(f"sparse payload K/2={K2} inconsistent with "
                             f"x's K={K}")
        if K % 8 != 0:
            raise ValueError(f"2:4 sparse GEMM needs K % 8 == 0, got {K}")
        if w_meta.shape != (G, K // 8, N) or w_meta.dtype != jnp.uint8:
            raise ValueError(f"w_meta must be uint8 ({G}, {K // 8}, {N}), "
                             f"got {w_meta.dtype} {w_meta.shape}")
    else:
        G, K2, N = w.shape
        assert K == K2, (x.shape, w.shape)
    if (wg_meta is not None) != (b_sparse and activation == "swiglu"):
        raise ValueError("wg_meta must be given iff b_sparse AND gated")
    if group_sizes.shape != (G,):
        raise ValueError(
            f"group_sizes must have shape ({G},) to match w's leading dim; "
            f"got {group_sizes.shape}"
        )
    has_gate = activation == "swiglu"
    if has_gate != (w_gate is not None):
        raise ValueError("w_gate must be given iff activation=='swiglu'")
    if (bg_scale is not None) != (has_gate and b_scale is not None):
        raise ValueError("bg_scale must be given iff gated AND b_scale is set")
    inject = abft is not None and abft.inject
    if inject != (fault_delta is not None):
        raise ValueError("fault operands must be given iff abft.inject")
    if a_scale is not None and a_scale.shape != (T, 1):
        raise ValueError(f"a_scale must be (T, 1)=({T}, 1), got {a_scale.shape}")
    if b_scale is not None and b_scale.shape != (G, 1, N):
        raise ValueError(f"b_scale must be (G, 1, N)=({G}, 1, {N}), got {b_scale.shape}")
    out_dtype = out_dtype or x.dtype

    bm_, bn_, bk_ = min(bm, T), min(bn, N), min(bk, K)
    if b_sparse and bk_ % 8 != 0:
        raise ValueError(f"2:4 sparse GEMM needs bk % 8 == 0, got {bk_}")
    # Sparse payload/metadata pad K in their own compressed units (the
    # K-pad is a multiple of 8 since K and bk both are); zero payload
    # expands to a zero dense block, so padded metadata is harmless.
    kpad = (-K) % bk_
    # pad rows *after* the data (group layout must keep row t at index t)
    x_p = jnp.pad(x, ((0, (-T) % bm_), (0, kpad)))
    w_p = jnp.pad(w, ((0, 0), (0, kpad // 2 if b_sparse else kpad),
                      (0, (-N) % bn_)))
    Tp, Kp = x_p.shape
    Np = w_p.shape[2]
    nk = Kp // bk_
    num_slots = Tp // bm_ + G  # static upper bound on (group, tile) pairs
    grid = (Np // bn_, num_slots, nk)

    grp, tile, first, starts, sizes = make_group_metadata(
        group_sizes, bm_, num_slots, Tp // bm_
    )

    wk_blk = bk_ // 2 if b_sparse else bk_
    in_specs = [
        # x block follows the slot's global row-tile; w follows its group.
        pl.BlockSpec((bm_, bk_), lambda j, l, k, grp, tile, first, st, sz: (tile[l], k)),
        pl.BlockSpec(
            (1, wk_blk, bn_), lambda j, l, k, grp, tile, first, st, sz: (grp[l], k, j)
        ),
    ]
    operands = [x_p, w_p]
    scratch = [pltpu.VMEM((bm_, bn_), jnp.float32)]
    if b_sparse:
        # packed indices: same per-expert grp[l] steering as the payload
        in_specs.append(pl.BlockSpec(
            (1, bk_ // 8, bn_),
            lambda j, l, k, grp, tile, first, st, sz: (grp[l], k, j)))
        operands.append(jnp.pad(
            w_meta, ((0, 0), (0, kpad // 8), (0, (-N) % bn_))))
    if has_gate:
        wg_p = jnp.pad(w_gate, ((0, 0), (0, kpad // 2 if b_sparse else kpad),
                                (0, (-N) % bn_)))
        in_specs.append(
            pl.BlockSpec(
                (1, wk_blk, bn_), lambda j, l, k, grp, tile, first, st, sz: (grp[l], k, j)
            )
        )
        operands.append(wg_p)
        if b_sparse:
            in_specs.append(pl.BlockSpec(
                (1, bk_ // 8, bn_),
                lambda j, l, k, grp, tile, first, st, sz: (grp[l], k, j)))
            operands.append(jnp.pad(
                wg_meta, ((0, 0), (0, kpad // 8), (0, (-N) % bn_))))
        scratch.append(pltpu.VMEM((bm_, bn_), jnp.float32))
    if a_scale is not None:
        # per-row scale panel follows the slot's global row-tile, like x
        in_specs.append(pl.BlockSpec(
            (bm_, 1), lambda j, l, k, grp, tile, first, st, sz: (tile[l], 0)))
        operands.append(jnp.pad(a_scale.astype(jnp.float32),
                                ((0, (-T) % bm_), (0, 0))))
    if b_scale is not None:
        bspec = pl.BlockSpec(
            (1, 1, bn_), lambda j, l, k, grp, tile, first, st, sz: (grp[l], 0, j))
        in_specs.append(bspec)
        operands.append(jnp.pad(b_scale.astype(jnp.float32),
                                ((0, 0), (0, 0), (0, (-N) % bn_))))
        if has_gate:
            in_specs.append(bspec)
            operands.append(jnp.pad(bg_scale.astype(jnp.float32),
                                    ((0, 0), (0, 0), (0, (-N) % bn_))))
    n_tiles = Tp // bm_
    grid_n = Np // bn_
    if inject:
        # Fault operands ride the slot's global row-tile, like x and the
        # flags: a straddled tile's visits all see the same fault.
        fspec = pl.BlockSpec(
            (1, 1), lambda j, l, k, grp, tile, first, st, sz: (tile[l], j))
        for arr, dt in ((fault_delta, jnp.float32), (fault_row, jnp.int32),
                        (fault_col, jnp.int32)):
            if arr.shape != (n_tiles, grid_n):
                raise ValueError(f"fault operand shape {arr.shape} != tile "
                                 f"grid ({n_tiles}, {grid_n})")
            in_specs.append(fspec)
            operands.append(arr.astype(dt))

    out_specs = pl.BlockSpec(
        (bm_, bn_), lambda j, l, k, grp, tile, first, st, sz: (tile[l], j)
    )
    out_shape = jax.ShapeDtypeStruct((Tp, Np), out_dtype)
    if abft is not None:
        out_specs = (out_specs, pl.BlockSpec(
            (1, 1), lambda j, l, k, grp, tile, first, st, sz: (tile[l], j)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((n_tiles, grid_n), jnp.int32))
        scratch.extend(abft_scratch(abft, bm_, bn_))

    kernel = functools.partial(
        _grouped_kernel,
        nk=nk,
        bm=bm_,
        out_dtype=out_dtype,
        activation=activation,
        has_gate=has_gate,
        has_a_scale=a_scale is not None,
        has_b_scale=b_scale is not None,
        abft=abft,
        b_sparse=b_sparse,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            gemm_vmem_bytes(
                bm_, bn_, bk_, a_bytes=x.dtype.itemsize,
                b_bytes=w.dtype.itemsize,
                out_bytes=jnp.dtype(out_dtype).itemsize, gated=has_gate,
                b_sparse=b_sparse, a_scale=a_scale is not None,
                b_scale=b_scale is not None)),
        interpret=interpret,
        name="mx_grouped_matmul",
    )(grp, tile, first, starts, sizes, *operands)
    # Rows not owned by any group (beyond sum(sizes)) are zero-filled INSIDE
    # the launch: the metadata steers spare dummy slots at the uncovered
    # tail tiles with an empty row mask + first-writer flag, so no
    # post-kernel masking pass (an extra M*N round-trip) is needed.
    if abft is not None:
        out, flags = out
        return out[:T, :N], flags
    return out[:T, :N]


def _ragged_dot_f32(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Memory-safe ragged dot (`lax.ragged_dot`): never materializes a
    (T, K, N) per-row weight gather (terabytes at real MoE sizes)."""
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)


def grouped_matmul_reference(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    *,
    w_gate: Optional[jax.Array] = None,
    activation: str = "none",
    out_dtype=None,
) -> jax.Array:
    """XLA reference semantics for the grouped matmul.  Used by the xla
    backend of ops.grouped_matmul and by the numerics tests."""
    T = x.shape[0]
    G = w.shape[0]
    out_dtype = out_dtype or x.dtype
    gs = group_sizes.astype(jnp.int32)
    h = _ragged_dot_f32(x, w, gs)
    if activation == "swiglu":
        g = _ragged_dot_f32(x, w_gate, gs)
        h = jax.nn.silu(g) * h
    else:
        h = apply_activation(h, activation)
    total = jnp.sum(gs) if G else jnp.int32(0)
    valid = jnp.arange(T, dtype=jnp.int32) < total
    return jnp.where(valid[:, None], h, 0).astype(out_dtype)
