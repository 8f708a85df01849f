"""Paged split-KV flash decode as an MX-pattern Pallas kernel.

One query token per slot attends over that slot's KV cache *pages*: the
physical cache is a flat (num_pages, page_size, Hkv, d) pool and each slot
names its pages through a (slots, W) page table.  The grid walks
(slot, page_slot) with the page table steered through scalar prefetch — the
SAME construction as the group-offset prefetch in `mx_grouped_matmul`: the
table rides to SMEM before the kernel body runs, so the BlockSpec index
maps can point the page DMAs at arbitrary pool pages while the current
page reduces (the double-buffered page fetch the zero-stall papers argue
for; Pallas' grid pipeline does the overlap).

Each grid step moves ONE whole page, every KV head of it, as a contiguous
(page_size * Hkv, d) block: the pool is viewed as (P, page_size * Hkv, d),
a free reshape, so the block's last two dims equal the array's — the shape
the TPU lowering accepts whatever Hkv is (a per-head (page_size, 1, d)
block is refused: its second-minor dim 1 is neither the array's Hkv nor a
multiple of 8).  All H query heads score the page's page_size * Hkv rows
in one dot, and a head-match mask keeps query head h on its kv head
h // groups — the cross-head products are masked like positions past the
live length.

The split-KV combine is the paper's inter-k-buffering discipline with
K := the page axis: online-softmax running statistics (m, l, acc) persist
in VMEM scratch across the page grid dimension, every resident page
streams through VMEM exactly once, and the finished output tile is written
back once at the last page (single write-back; Table II).

Pages PAST a slot's live length are masked by position, not skipped: the
table pads with the allocator's dump page so every steered DMA is
in-bounds, and masked lanes contribute exp(-inf) == 0.  An int8 KV cache
passes per-row dequant scale pages (`k_scale`/`v_scale`) that are steered
by the same table; a row's scale multiplies its score column (k) and its
probability column (v), which is the dequantized dot reassociated.

Oracle: `kernels.ref.paged_decode_ref` (the gather-based jnp formulation,
the CPU path of the model stack).

`mx_flash_verify` is the speculative-decoding widening of the same kernel:
S = k+1 query rows per slot (the draft window plus the committed token)
ride ONE launch — same scalar-prefetched page table, same online-softmax
scratch discipline, same single write-back — with a causal-within-window
mask so row r attends positions <= lengths[i] - S + r.  Verifying k drafts
re-reads the resident pages and the weights ONCE instead of k+1 times,
which is the paper's tile-buffer data-reuse argument applied along the
time axis.  Decode is the S = 1 window.  Oracle:
`kernels.ref.paged_prefill_ref` at index = lengths - S.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jax.lax.Precision.HIGHEST


def _window_kernel(
    # scalar-prefetch refs (SMEM):
    pt_ref, len_ref,
    # tensor refs:
    *refs,
    nj: int, ps: int, S: int, scale: float, out_dtype, has_scales: bool,
):
    it = iter(refs)
    q_ref = next(it)
    k_ref = next(it)
    v_ref = next(it)
    ks_ref = next(it) if has_scales else None
    vs_ref = next(it) if has_scales else None
    rh_ref = next(it)  # (S*H, 1) kv head of each query row
    ro_ref = next(it)  # (S*H, 1) window offset r of each query row
    ch_ref = next(it)  # (1, ps*Hkv) kv head of each page row
    cp_ref = next(it)  # (1, ps*Hkv) position of each page row in its page
    o_ref = next(it)
    m_ref = next(it)
    l_ref = next(it)
    acc_ref = next(it)

    i = pl.program_id(0)  # slot
    j = pl.program_id(1)  # page slot (split-KV axis)

    @pl.when(j == 0)
    def _init():  # C-tile reset analogue, per slot
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)   # (S*H, d) every query row
    k = k_ref[0].astype(jnp.float32)   # (ps*Hkv, d) one resident page
    v = v_ref[0].astype(jnp.float32)

    # f32 scores and probabilities at f32 precision (the TPU default would
    # contract them in bf16 passes)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                precision=_F32) * scale
    if has_scales:  # int8 pages: a row's dequant scale scales its column
        s = s * ks_ref[0]
    # row r*H + h is query head h of window row r, at absolute position
    # lengths[i] - S + r; it sees page rows of its own kv head at
    # positions <= its own.  A free slot (length 0) masks every lane, so
    # the m_safe guard below yields zero output rows.
    kpos = j * ps + cp_ref[...]
    keep = (rh_ref[...] == ch_ref[...]) & (kpos <= len_ref[i] - S + ro_ref[...])
    s = jnp.where(keep, s, -jnp.inf)

    m_prev = m_ref[...]  # (S*H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(s - m_safe)  # masked lanes: exp(-inf - finite) == 0
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    pv = p * vs_ref[0] if has_scales else p
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pv, v, preferred_element_type=jnp.float32, precision=_F32
    )
    m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _store():  # single write-back of the combined split-KV partials
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(out_dtype)


def _paged_window_attention(q, k_pages, v_pages, page_table, lengths,
                            k_scale, v_scale, *, interpret: bool, name: str):
    """q: (B, S, H, d) -> (B, S, H, d); see `mx_flash_verify`."""
    B, S, H, d = q.shape
    P, ps, Hkv, d2 = k_pages.shape
    if d2 != d or v_pages.shape != k_pages.shape:
        raise ValueError(f"q {q.shape} vs pages {k_pages.shape}/{v_pages.shape}")
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"page_table must be (B, W), got {page_table.shape}")
    has_scales = k_scale is not None
    if has_scales != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if has_scales and k_scale.shape != (P, ps, Hkv):
        raise ValueError(
            f"scales must be (P, ps, Hkv)={(P, ps, Hkv)}, got {k_scale.shape}"
        )
    G = H // Hkv
    W = page_table.shape[1]
    R, C = S * H, ps * Hkv  # query rows per slot, pool rows per page

    # Row and column labels of the (R, C) score tile, built once on the
    # host: constant block indices, so the pipeline fetches them once.
    r = np.arange(R)
    c = np.arange(C)
    labels = [
        jnp.asarray(((r % H) // G).reshape(R, 1), jnp.int32),
        jnp.asarray((r // H).reshape(R, 1), jnp.int32),
        jnp.asarray((c % Hkv).reshape(1, C), jnp.int32),
        jnp.asarray((c // Hkv).reshape(1, C), jnp.int32),
    ]
    const = lambda i, j, pt, ln: (0, 0)  # noqa: E731
    page = lambda i, j, pt, ln: (pt[i, j], 0, 0)  # noqa: E731

    in_specs = [
        pl.BlockSpec((1, R, d), lambda i, j, pt, ln: (i, 0, 0)),
        # page DMAs steered by the prefetched table (cf. grp[l] in
        # mx_grouped_matmul): page slot j of slot i loads pool page pt[i, j]
        pl.BlockSpec((1, C, d), page),
        pl.BlockSpec((1, C, d), page),
    ]
    operands = [q.reshape(B, R, d), k_pages.reshape(P, C, d),
                v_pages.reshape(P, C, d)]
    if has_scales:
        in_specs += [pl.BlockSpec((1, 1, C), page)] * 2
        operands += [k_scale.astype(jnp.float32).reshape(P, 1, C),
                     v_scale.astype(jnp.float32).reshape(P, 1, C)]
    in_specs += [pl.BlockSpec((R, 1), const)] * 2
    in_specs += [pl.BlockSpec((1, C), const)] * 2
    operands += labels

    out = pl.pallas_call(
        functools.partial(
            _window_kernel, nj=W, ps=ps, S=S, scale=1.0 / math.sqrt(d),
            out_dtype=q.dtype, has_scales=has_scales,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, W),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, R, d), lambda i, j, pt, ln: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),  # m — running max
                pltpu.VMEM((R, 1), jnp.float32),  # l — running normalizer
                pltpu.VMEM((R, d), jnp.float32),  # acc — the split-KV buffer
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(B, S, H, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mx_flash_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash decode.  q: (B, H, d) one token per slot; k_pages /
    v_pages: (P, page_size, Hkv, d) flat page pools; page_table: (B, W)
    int32 physical page ids (entries past a slot's pages must still be
    valid ids — the allocator pads with its dump page); lengths: (B,) live
    token counts (a slot attends over positions [0, lengths[i])); 0 marks
    a free slot, which produces an all-zero output row.

    GQA: H == Hkv * groups with query head h served by kv head h // groups
    (the `_repeat_kv` layout).  int8 caches pass `k_scale` / `v_scale` of
    shape (P, page_size, Hkv) — per-row dequant scales steered by the same
    page table.  Returns (B, H, d) in q's dtype.
    """
    out = _paged_window_attention(q[:, None], k_pages, v_pages, page_table,
                                  lengths, k_scale, v_scale,
                                  interpret=interpret, name="mx_flash_decode")
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mx_flash_verify(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Batched-verify paged attention: S query rows per slot in ONE launch.

    q: (B, S, H, d) — the S = k+1 speculative window per slot (its K/V rows
    must already be written into the pages, like the prefill-into-pages
    path); k_pages / v_pages: (P, page_size, Hkv, d) flat page pools;
    page_table: (B, W) physical page ids; lengths: (B,) live token counts
    INCLUDING the window (query row r sits at position lengths[i] - S + r
    and attends positions <= its own).  lengths 0 marks a free slot, which
    produces all-zero output rows.  GQA and int8 scale pages exactly as
    `mx_flash_decode`.  Returns (B, S, H, d) in q's dtype.
    """
    return _paged_window_attention(q, k_pages, v_pages, page_table, lengths,
                                   k_scale, v_scale, interpret=interpret,
                                   name="mx_flash_verify")
