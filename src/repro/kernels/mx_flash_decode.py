"""Paged split-KV flash decode as an MX-pattern Pallas kernel.

One query token per slot attends over that slot's KV cache *pages*: the
physical cache is a layer-stacked (L, num_pages, page_size, Hkv * d) pool,
one token's kv heads side by side in a row, and each slot names its pages
through a (slots, W) page table.  The kernel reads layer ``layer`` of the
pool where it lies: the layer index is one more scalar-prefetch operand and
picks the page operands' layer in their index maps, so the model's layer
scan carries the stacked pool and no layer is ever sliced out of it.  Each
grid step reduces a block of ``ppb`` pages of one slot in one pair of dots,
the (S * H) x (ppb * page_size) scores and then P . V.

**Lane-dense rows.**  A page arrives as (page_size, Hkv * d), 128 lanes or
more at the served widths, so neither the pool nor a page block is padded
to the lane width.  Query head h is placed in its kv head's lanes of a
zeroed (Hkv * d) row (`_spread_heads`): its dot with a pool row is then that
head's score, the other kv heads' lanes adding exact zeros.  P . V gives a
(Hkv * d) row of which the head keeps its own lanes.  The FLOPs are those of
one (ps * Hkv, d) dot per page, and no head-match mask is needed.

**Live blocks only.**  A slot's live pages are j < ceil(lengths[i] /
page_size).  The grid has one step per block that holds any of them, slot
by slot (`_schedule`, built per call from the lengths; the grid's size is
that traced count, at least one).  A block past a slot's length and a free
slot (length 0) get no step and read nothing; a free slot's output rows
are zero.

**Page copies.**  A block's pages arrive as ``ppb`` page operands, one
contiguous (page_size, Hkv * d) page each.  Their copies are steered by a
scalar-prefetched *fetch table* built from the page table — the SAME
construction as the group-offset prefetch in `mx_grouped_matmul` — and
ride the grid pipeline, which double-buffers them: the next step's pages
are in flight while this step reduces, and an operand whose page does not
change is not copied again.  In a slot's last block the operands past its
live pages point at the page they load next, so that copy starts early and
no copy reads a page past a slot's length.  Rows past the length, in the
last live page and in those operands, are masked by position (their
probabilities are exactly 0; a pool page is always finite).

**Block size.**  ``ppb`` follows the shape: about `BLOCK_ROWS` (token, kv
head) pairs per block — 16 pages at page_size 16 and Hkv 2, 4 at Hkv 8 —
capped by W and by the VMEM the block needs
(`kernels/vmem.paged_attention_vmem_bytes`).  W need not be a multiple of
``ppb``: a last block's pages past W are never live.

The split-KV combine is the paper's inter-k-buffering discipline with
K := the page axis: online-softmax running statistics (m, l, acc) persist
in VMEM scratch across a slot's steps, every live page streams through
VMEM exactly once, and the finished output tile is written back once at
the slot's last block (single write-back; Table II).

An int8 KV cache passes per-(token, kv head) dequant scale pools
(`k_scale`/`v_scale`, (L, P, page_size, Hkv)) steered by the same table:
the table's scale rows are gathered per call (a sixteenth of the payload at
d = 64 in int8) into one block of rows a step, and a query row's kv head
scale multiplies its score column (k) and its probability column (v),
which is the dequantized dot reassociated.

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
# (token, kv head) pairs one block holds (page_size * Hkv a page): enough
# that the block's two dots, not the grid step, set its time.
BLOCK_ROWS = 512
# VMEM a block may claim before ppb shrinks (the GEMM planner's budget).
_VMEM_BUDGET = 64 * 1024 * 1024


def pages_per_block(W: int, rows: int, ps: int, Hkv: int, d: int,
                    **sizes) -> int:
    """Pages one grid step reduces: `BLOCK_ROWS` (token, kv head) pairs,
    capped by the table width W and by `_VMEM_BUDGET` (``sizes``: the
    byte counts `paged_attention_vmem_bytes` takes)."""
    ppb = max(1, min(W, BLOCK_ROWS // (ps * Hkv)))
    while ppb > 1 and paged_attention_vmem_bytes(
            rows, ps, Hkv * d, ppb, **sizes) > _VMEM_BUDGET:
        ppb -= 1
    return ppb


def _spread_heads(q, Hkv: int):
    """(..., H, d) -> (..., H, Hkv * d): query head h in its kv head
    h // G's lanes, zeros elsewhere (GQA groups G = H // Hkv)."""
    H, d = q.shape[-2:]
    own = (jnp.arange(H) // (H // Hkv))[:, None] == jnp.arange(Hkv)
    spread = jnp.where(own[:, :, None], q[..., None, :], jnp.zeros((), q.dtype))
    return spread.reshape(*q.shape[:-1], Hkv * d)


def _own_lanes(o, Hkv: int):
    """(..., H, Hkv * d) -> (..., H, d): each head's own kv head's lanes."""
    H = o.shape[-2]
    o = o.reshape(*o.shape[:-1], Hkv, -1)
    heads = jnp.arange(H)
    return o[..., heads, heads // (H // Hkv), :]


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
    layer_ref, fetch_ref, slot_ref, blk_ref, len_ref, npg_ref,
    # tensor refs:
    *refs, ppb: int, ps: int, S: int, H: int, Hkv: int, scale: float,
    has_scales: bool,
):
    del layer_ref, fetch_ref  # steer the page operands' index maps only
    it = iter(refs)
    q_ref = next(it)                          # (1, R, Hkv*d) every query row
    k_refs = [next(it) for _ in range(ppb)]   # (ps, Hkv*d) a page each
    v_refs = [next(it) for _ in range(ppb)]
    sc_ref = next(it) if has_scales else None  # (1, 2*Hkv, M) k, v scales
    pos_ref = next(it)  # (R, M) block position less the row's window offset
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
        q = q_ref[0].astype(jnp.float32)  # (R, Hkv*d)
        k = jnp.concatenate([r[...].astype(jnp.float32) for r in k_refs])
        v = jnp.concatenate([r[...].astype(jnp.float32) for r in v_refs])

        # f32 scores and probabilities at f32 precision (the TPU default
        # would contract them in bf16 passes)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=_F32) * scale
        if has_scales:
            # int8 pages: row r's kv head's scale of a token scales its
            # score column (k) and its probability column (v)
            sc = sc_ref[0]
            head = (jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
                    % H) // (H // Hkv)
            ks = vs = jnp.zeros_like(s)
            for g in range(Hkv):
                ks = jnp.where(head == g, sc[g:g + 1], ks)
                vs = jnp.where(head == g, sc[Hkv + g:Hkv + g + 1], vs)
            s = s * ks
        # row r*H + h is query head h of window row r, at absolute position
        # lengths[i] - S + r; it sees block rows at positions <= its own
        keep = jb * (ppb * ps) + pos_ref[...] <= len_ref[i] - S
        s = jnp.where(keep, s, -jnp.inf)

        m_prev = m_ref[...]  # (R, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        p = jnp.exp(s - m_safe)  # masked lanes: exp(-inf - finite) == 0
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = p * vs if has_scales else p
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            pv, v, preferred_element_type=jnp.float32, precision=_F32
        )
        m_ref[...] = m_new

    @pl.when((jb + 1) * ppb >= npg_ref[i])
    def _store():  # single write-back of the combined split-KV partials
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _paged_window_attention(q, k_pages, v_pages, layer, page_table, lengths,
                            k_scale, v_scale, *, interpret: bool, name: str):
    """q: (B, S, H, d) -> (B, S, H, d); see `mx_flash_verify`."""
    B, S, H, d = q.shape
    L, P, ps, lanes = k_pages.shape
    if lanes % d or v_pages.shape != k_pages.shape:
        raise ValueError(f"q {q.shape} vs pages {k_pages.shape}/{v_pages.shape}")
    Hkv = lanes // d
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"page_table must be (B, W), got {page_table.shape}")
    has_scales = k_scale is not None
    if has_scales != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if has_scales and k_scale.shape != (L, P, ps, Hkv):
        raise ValueError(f"scales must be (L, P, ps, Hkv)={(L, P, ps, Hkv)}, "
                         f"got {k_scale.shape}")
    W = page_table.shape[1]
    R = S * H  # query rows per slot
    sizes = dict(kv_bytes=jnp.dtype(k_pages.dtype).itemsize,
                 q_bytes=jnp.dtype(q.dtype).itemsize,
                 scale_rows=2 * Hkv if has_scales else 0)
    ppb = pages_per_block(W, R, ps, Hkv, d, **sizes)
    nb = -(-W // ppb)
    M = ppb * ps  # pool rows a block

    # Query row r = window row r // H; block row c is block position c.  The
    # label of (r, c): that position less the row's window offset — keep
    # iff block start + label <= lengths - S.  Built once on the host: a
    # constant block index, so the pipeline fetches it once.
    label = np.arange(M)[None, :] - np.arange(R)[:, None] // H

    lengths = lengths.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    steps, slot, blk, npg, fetch = _schedule(page_table, lengths, ps, ppb, nb)

    per_slot = lambda t, lay, f, sl, *_: (sl[t], 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, R, lanes), per_slot)]
    # page operand p of step t loads page fetch[t * ppb + p] of the layer
    in_specs += [pl.BlockSpec(
        (pl.squeezed, pl.squeezed, ps, lanes),
        lambda t, lay, f, *_, p=p: (lay[0], f[t * ppb + p], 0, 0))
        for p in range(ppb)] * 2
    operands = [_spread_heads(q, Hkv).reshape(B, R, lanes)]
    operands += [k_pages] * ppb + [v_pages] * ppb
    if has_scales:
        # the table's scale rows, a block each: (B * nb, 2 * Hkv, M), k
        # then v
        sc = jnp.stack([k_scale[layer[0], fetch], v_scale[layer[0], fetch]])
        sc = sc.astype(jnp.float32).reshape(2, B * nb, M, Hkv)
        operands.append(sc.transpose(1, 0, 3, 2).reshape(B * nb, 2 * Hkv, M))
        in_specs.append(
            pl.BlockSpec((1, 2 * Hkv, M), lambda t, *_: (t, 0, 0)))
    in_specs.append(pl.BlockSpec((R, M), lambda t, *_: (0, 0)))
    operands.append(jnp.asarray(label, jnp.int32))

    out = pl.pallas_call(
        functools.partial(
            _window_kernel, ppb=ppb, ps=ps, S=S, H=H, Hkv=Hkv,
            scale=1.0 / math.sqrt(d), has_scales=has_scales,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(steps,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, R, lanes), per_slot),
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),      # m — running max
                pltpu.VMEM((R, 1), jnp.float32),      # l — running normalizer
                pltpu.VMEM((R, lanes), jnp.float32),  # acc — split-KV buffer
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, lanes), q.dtype),
        compiler_params=compiler_params(
            ("arbitrary",),
            paged_attention_vmem_bytes(R, ps, lanes, ppb, **sizes)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name,
    )(layer, fetch, slot, blk, lengths, npg, *operands)
    # a free slot has no step: its rows are zero
    out = jnp.where((lengths > 0)[:, None, None], out, jnp.zeros((), q.dtype))
    return _own_lanes(out.reshape(B, S, H, lanes), Hkv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mx_flash_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash decode.  q: (B, H, d) one token per slot; k_pages /
    v_pages: (L, P, page_size, Hkv * d) layer-stacked page pools; layer:
    the (scalar, int32) layer whose pages are read; page_table: (B, W)
    int32 physical page ids (valid ids throughout: the allocator pads with
    its dump page; the kernel reads only each slot's live pages); lengths:
    (B,) live token counts (a slot attends over positions [0, lengths[i]));
    0 marks a free slot, which produces an all-zero output row.

    GQA: H == Hkv * groups with query head h served by kv head h // groups
    (the `_repeat_kv` layout); a pool row holds kv head g in lanes
    [g * d, (g + 1) * d).  int8 caches pass `k_scale` / `v_scale` of shape
    (L, P, page_size, Hkv) — per-row dequant scales steered by the same
    page table.  Returns (B, H, d) in q's dtype.
    """
    out = _paged_window_attention(q[:, None], k_pages, v_pages, layer,
                                  page_table, lengths, k_scale, v_scale,
                                  interpret=interpret, name="mx_flash_decode")
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mx_flash_verify(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer: jax.Array,
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
    path); k_pages / v_pages: (L, P, page_size, Hkv * d) layer-stacked page
    pools, read at ``layer``; page_table: (B, W) physical page ids;
    lengths: (B,) live token counts INCLUDING the window (query row r sits
    at position lengths[i] - S + r and attends positions <= its own).
    lengths 0 marks a free slot, which produces all-zero output rows.  GQA
    and int8 scale pages exactly as `mx_flash_decode`.  Returns
    (B, S, H, d) in q's dtype.
    """
    return _paged_window_attention(q, k_pages, v_pages, layer, page_table,
                                   lengths, k_scale, v_scale,
                                   interpret=interpret,
                                   name="mx_flash_verify")
