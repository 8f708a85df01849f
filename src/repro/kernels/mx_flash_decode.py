"""Paged split-KV flash decode as an MX-pattern Pallas kernel.

One query token per slot attends over that slot's KV cache *pages*: the
physical cache is a flat (num_pages, page_size, Hkv, d) pool and each slot
names its pages through a (slots, W) page table.  Each grid step reduces a
block of ``ppb`` pages of one slot in one pair of dots, the
(S * H) x (ppb * page_size * Hkv) scores and then P . V.

**Live blocks only.**  A slot's live pages are j < ceil(lengths[i] /
page_size).  The grid has one step per block that holds any of them, slot
by slot (`_schedule`, built per call from the lengths; the grid's size is
that traced count, at least one).  A block past a slot's length and a free
slot (length 0) get no step and read nothing; a free slot's output rows
are zero.

**Page copies.**  The pool is viewed as (P, page_size * Hkv, d), a free
reshape, and a block's pages arrive as ``ppb`` page operands, one
contiguous (page_size * Hkv, d) page each, all kv heads of it.  Their
copies are steered by a scalar-prefetched *fetch table* built from the
page table — the SAME construction as the group-offset prefetch in
`mx_grouped_matmul` — and ride the grid pipeline, which double-buffers
them: the next step's pages are in flight while this step reduces, and an
operand whose page does not change is not copied again.  In a slot's last
block the operands past its live pages point at the page they load next,
so that copy starts early and no copy reads a page past a slot's length.
Rows past the length, in the last live page and in those operands, are
masked by position (their probabilities are exactly 0; a pool page is
always finite).  The pipeline, not hand-written copies: a hand-sliced copy
out of a pool whose rows are narrower than 128 lanes is refused by Mosaic,
and a lane-dense copy of the pool would cost two pool-sized relayouts per
operand and layer.

**Block size.**  ``ppb`` follows the shape: about `BLOCK_ROWS` pool rows
(page_size * Hkv a page) per block — 16 pages at page_size 16 and Hkv 2, 4
at Hkv 8 — capped by W and by the VMEM the block needs
(`kernels/vmem.paged_attention_vmem_bytes`).  W need not be a multiple of
``ppb``: a last block's pages past W are never live.

All H query heads score a block's rows in one dot, and a head-match mask
keeps query head h on its kv head h // groups — the cross-head products are
masked like positions past the live length.

The split-KV combine is the paper's inter-k-buffering discipline with
K := the page axis: online-softmax running statistics (m, l, acc) persist
in VMEM scratch across a slot's steps, every live page streams through
VMEM exactly once, and the finished output tile is written back once at
the slot's last block (single write-back; Table II).

An int8 KV cache passes per-row dequant scale pages (`k_scale`/`v_scale`)
steered by the same table: the table's scale rows are gathered per call (a
sixteenth of the payload at d = 64 in int8) into one lane-dense row a
block, and a row's scale multiplies its score column (k) and its
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

from .vmem import compiler_params, paged_attention_vmem_bytes

_F32 = jax.lax.Precision.HIGHEST
# Pool rows (page_size * Hkv a page) one block holds: enough that the
# block's two dots, not the grid step, set its time.
BLOCK_ROWS = 512
# VMEM a block may claim before ppb shrinks (the GEMM planner's budget).
_VMEM_BUDGET = 64 * 1024 * 1024
# Label of a score the query row may never see (another kv head): past any
# position a slot reaches.
_NEVER = 1 << 30


def pages_per_block(W: int, rows: int, ps: int, Hkv: int, d: int, *,
                    kv_bytes: int, q_bytes: int, scales: bool) -> int:
    """Pages one grid step reduces: `BLOCK_ROWS` pool rows, capped by the
    table width W and by `_VMEM_BUDGET`."""
    ppb = max(1, min(W, BLOCK_ROWS // (ps * Hkv)))
    while ppb > 1 and paged_attention_vmem_bytes(
            rows, ps * Hkv, d, ppb, kv_bytes=kv_bytes, q_bytes=q_bytes,
            scales=scales) > _VMEM_BUDGET:
        ppb -= 1
    return ppb


def _schedule(page_table, lengths, ps: int, ppb: int, nb: int):
    """The walk: one grid step per live block, slot by slot.

    Returns the step count (at least one), each step's slot and block
    (arrays of the largest count, B * nb), each slot's live pages, and the
    fetch table (steps * ppb,) that steers page operand p of every step:
    the step's page where it is live; else the page the operand loads at
    its next live step — block 0 of the next slot with more than p live
    pages — so its copy runs early and only once; else, past its last live
    step, the page it loaded then.  So no copy reads a page past a slot's
    length."""
    B, W = page_table.shape
    table = page_table.astype(jnp.int32)
    npg = jnp.minimum(-(-lengths // ps), W)
    nblk = -(-npg // ppb)
    ends = jnp.cumsum(nblk)
    t = jnp.arange(B * nb)
    slot = jnp.minimum((ends <= t[:, None]).sum(axis=1), B - 1)
    blk = jnp.clip(t - (ends - nblk)[slot], 0, nb - 1)
    p = jnp.arange(ppb)
    col = blk[:, None] * ppb + p                       # (B * nb, ppb)
    live = col < npg[slot][:, None]
    rows = jnp.arange(B)[:, None]
    has = npg[:, None] > p                             # (B, ppb)
    nxt = jax.lax.cummin(jnp.where(has, rows, B), axis=0, reverse=True)
    nxt = jnp.concatenate([nxt[1:], jnp.full((1, ppb), B)])[slot[:, None], p]
    last = jnp.max(jnp.where(has, rows, -1), axis=0)   # (ppb,)
    last_col = (npg[last] - 1 - p) // ppb * ppb + p
    # an operand no slot reaches keeps the first step's first page
    src_slot = jnp.where(live, slot[:, None], jnp.where(
        nxt < B, nxt, jnp.where(last >= 0, last, slot[0])))
    src_col = jnp.where(live, col, jnp.where(
        nxt < B, p, jnp.where(last >= 0, last_col, 0)))
    fetch = table[src_slot, jnp.minimum(src_col, W - 1)]
    steps = jnp.maximum(ends[-1], 1).astype(jnp.int32)
    return (steps, slot.astype(jnp.int32), blk.astype(jnp.int32), npg,
            fetch.reshape(-1))


def _window_kernel(
    # scalar-prefetch refs (SMEM):
    fetch_ref, slot_ref, blk_ref, len_ref, npg_ref,
    # tensor refs:
    *refs, ppb: int, ps: int, S: int, scale: float, has_scales: bool,
):
    del fetch_ref  # steers the page operands' index maps only
    it = iter(refs)
    q_ref = next(it)                          # (1, R, d) every query row
    k_refs = [next(it) for _ in range(ppb)]   # (1, C, d) a page each
    v_refs = [next(it) for _ in range(ppb)]
    sc_ref = next(it) if has_scales else None  # (1, 2, M) k, v scale rows
    pos_ref = next(it)  # (R, M) block position a score reads, or _NEVER
    o_ref = next(it)
    m_ref = next(it)
    l_ref = next(it)
    acc_ref = next(it)

    t = pl.program_id(0)  # step: one live block of one slot
    i, jb = slot_ref[t], blk_ref[t]

    @pl.when(jb == 0)
    def _init():  # C-tile reset analogue, per slot
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every step is a live block, but for the one step of a batch with no
    # live slot
    @pl.when(jb * ppb < npg_ref[i])
    def _reduce():
        q = q_ref[0].astype(jnp.float32)  # (R, d)
        k = jnp.concatenate([r[0].astype(jnp.float32) for r in k_refs])
        v = jnp.concatenate([r[0].astype(jnp.float32) for r in v_refs])

        # f32 scores and probabilities at f32 precision (the TPU default
        # would contract them in bf16 passes)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=_F32) * scale
        if has_scales:  # int8 pages: a row's dequant scale scales its column
            s = s * sc_ref[0, 0:1]
        # row r*H + h is query head h of window row r, at absolute position
        # lengths[i] - S + r; it sees block rows of its own kv head at
        # positions <= its own.  The label folds the window offset and the
        # head match in.
        keep = jb * (ppb * ps) + pos_ref[...] <= len_ref[i] - S
        s = jnp.where(keep, s, -jnp.inf)

        m_prev = m_ref[...]  # (R, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        p = jnp.exp(s - m_safe)  # masked lanes: exp(-inf - finite) == 0
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = p * sc_ref[0, 1:2] if has_scales else p
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            pv, v, preferred_element_type=jnp.float32, precision=_F32
        )
        m_ref[...] = m_new

    @pl.when((jb + 1) * ppb >= npg_ref[i])
    def _store():  # single write-back of the combined split-KV partials
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


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
    sizes = dict(kv_bytes=jnp.dtype(k_pages.dtype).itemsize,
                 q_bytes=jnp.dtype(q.dtype).itemsize, scales=has_scales)
    ppb = pages_per_block(W, R, ps, Hkv, d, **sizes)
    nb = -(-W // ppb)
    M = ppb * C  # pool rows a block

    # Query row r = window row r // H, head r % H, kv head g; block row c is
    # kv head c % Hkv at block position c // Hkv.  The label of (r, c): that
    # position less the row's window offset, or _NEVER where the kv heads
    # differ — keep iff block start + label <= lengths - S.  Built once on
    # the host: a constant block index, so the pipeline fetches it once.
    r = np.arange(R)[:, None]
    c = np.arange(M)[None, :]
    label = np.where(c % Hkv == (r % H) // G, c // Hkv - r // H, _NEVER)

    lengths = lengths.astype(jnp.int32)
    steps, slot, blk, npg, fetch = _schedule(page_table, lengths, ps, ppb, nb)

    per_slot = lambda t, f, sl, *_: (sl[t], 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, R, d), per_slot)]
    # page operand p of step t loads pool page fetch[t * ppb + p]
    in_specs += [pl.BlockSpec(
        (1, C, d), lambda t, f, *_, p=p: (f[t * ppb + p], 0, 0))
        for p in range(ppb)] * 2
    operands = [q.reshape(B, R, d)]
    operands += [k_pages.reshape(P, C, d)] * ppb
    operands += [v_pages.reshape(P, C, d)] * ppb
    if has_scales:
        # the table's scale rows, a block each: (B * nb, 2, M), k then v
        sc = jnp.stack([k_scale, v_scale]).astype(jnp.float32)
        sc = sc.reshape(2, P, C)[:, fetch].reshape(2, B * nb, M)
        operands.append(sc.transpose(1, 0, 2))
        in_specs.append(pl.BlockSpec((1, 2, M), lambda t, *_: (t, 0, 0)))
    in_specs.append(pl.BlockSpec((R, M), lambda t, *_: (0, 0)))
    operands.append(jnp.asarray(label, jnp.int32))

    out = pl.pallas_call(
        functools.partial(
            _window_kernel, ppb=ppb, ps=ps, S=S, scale=1.0 / math.sqrt(d),
            has_scales=has_scales,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, R, d), per_slot),
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),  # m — running max
                pltpu.VMEM((R, 1), jnp.float32),  # l — running normalizer
                pltpu.VMEM((R, d), jnp.float32),  # acc — the split-KV buffer
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, d), q.dtype),
        compiler_params=compiler_params(
            ("arbitrary",),
            paged_attention_vmem_bytes(R, C, d, ppb, **sizes)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name,
    )(fetch, slot, blk, lengths, npg, *operands)
    # a free slot has no step: its rows are zero
    out = jnp.where((lengths > 0)[:, None, None], out, jnp.zeros((), q.dtype))
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
    int32 physical page ids (valid ids throughout: the allocator pads with
    its dump page; the kernel reads only each slot's live pages); lengths:
    (B,) live token counts (a slot attends over positions [0, lengths[i]));
    0 marks a free slot, which produces an all-zero output row.

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
