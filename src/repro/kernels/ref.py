"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` is the semantic ground truth its kernel is tested against
(interpret-mode allclose sweeps in tests/test_kernels_*.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The paged attention oracles compute in f32 and contract at f32 precision
# on every backend (a TPU's default would use bf16 passes).
_F32 = jax.lax.Precision.HIGHEST


def matmul_ref(a: jax.Array, b: jax.Array, out_dtype=None) -> jax.Array:
    """D = A @ B with f32 accumulation (the MX semantic: full-precision
    accumulation in the near-FPU buffer, single write-back)."""
    out_dtype = out_dtype or a.dtype
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def matmul_bias_ref(a, b, c, out_dtype=None):
    """GEMM with C != 0 (the paper's general Eq. 1)."""
    out_dtype = out_dtype or a.dtype
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return (acc + c.astype(jnp.float32)).astype(out_dtype)


def baseline_matmul_ref(a, b, bk: int, out_dtype=None):
    """Oracle for the *baseline* kernel: partial sums round-trip through the
    output buffer in the output dtype every bk-chunk (no inter-k buffering).
    For f32 outputs this equals matmul_ref; for narrow dtypes it exposes the
    accumulation-precision loss the MX buffer avoids."""
    out_dtype = out_dtype or a.dtype
    K = a.shape[-1]
    nk = -(-K // bk)
    out = jnp.zeros((*a.shape[:-1], b.shape[-1]), out_dtype)
    for ki in range(nk):
        a_blk = a[..., ki * bk : (ki + 1) * bk]
        b_blk = b[ki * bk : (ki + 1) * bk, :]
        part = jnp.dot(a_blk, b_blk, preferred_element_type=jnp.float32)
        out = (out.astype(jnp.float32) + part).astype(out_dtype)
    return out


def ssd_scan_ref(x, a_log, b, c, chunk: int):
    """Mamba-2 SSD (state-space dual) oracle, chunked semantics.

    Shapes (single head):
      x:     (L, P)   input projected to head dim P
      a_log: (L,)     log of the per-step scalar decay (a_t = exp(a_log_t) in (0,1])
      b:     (L, S)   input->state projection   (S = ssm state size)
      c:     (L, S)   state->output projection
    Returns y: (L, P) with  h_t = a_t * h_{t-1} + b_t^T x_t ;  y_t = c_t h_t.

    The chunked algorithm (intra-chunk quadratic + inter-chunk recurrence) is
    what the kernel implements; this oracle is the exact sequential scan, so
    it validates both the math and the chunking.
    """
    L, P = x.shape
    S = b.shape[-1]

    def step(h, inp):
        xt, alog_t, bt, ct = inp
        a_t = jnp.exp(alog_t)
        h = a_t * h + jnp.outer(bt, xt)  # (S, P)
        y = ct @ h  # (P,)
        return h, y

    h0 = jnp.zeros((S, P), jnp.float32)
    _, y = jax.lax.scan(
        step, h0, (x.astype(jnp.float32), a_log.astype(jnp.float32),
                   b.astype(jnp.float32), c.astype(jnp.float32))
    )
    return y.astype(x.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """Numerically-stable softmax attention oracle. q,k,v: (L, H) single head."""
    Lq, d = q.shape
    Lk = k.shape[0]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    s = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (p @ v.astype(jnp.float32)).astype(q.dtype)


def _gather_pages(k_pages, v_pages, layer, page_table, d, k_scale, v_scale):
    """Each slot's (W * ps) logical KV rows of layer ``layer``, f32 and
    dequantized, as (B, W * ps, Hkv, d): one gather per operand from the
    stacked pool at (layer, page) — no layer of the pool is sliced out —
    and only the gathered rows are reshaped into heads."""
    B, W = page_table.shape
    ps, lanes = k_pages.shape[2:]
    Hkv = lanes // d
    k = k_pages[layer, page_table].astype(jnp.float32)  # (B, W, ps, Hkv*d)
    v = v_pages[layer, page_table].astype(jnp.float32)
    k = k.reshape(B, W * ps, Hkv, d)
    v = v.reshape(B, W * ps, Hkv, d)
    if k_scale is not None:
        k = k * k_scale[layer, page_table].reshape(B, W * ps, Hkv, 1)
        v = v * v_scale[layer, page_table].reshape(B, W * ps, Hkv, 1)
    return k, v


def paged_decode_ref(q, k_pages, v_pages, layer, page_table, lengths, *,
                     k_scale=None, v_scale=None):
    """Gather-based oracle for `mx_flash_decode` — and the XLA fallback the
    model stack runs off-TPU.

    q: (B, H, d) one token per slot; k_pages / v_pages: (L, P, ps, Hkv * d)
    layer-stacked page pools, read at ``layer``; page_table: (B, W)
    physical page ids; lengths: (B,) live token counts (0 = free slot ->
    zero output row).  Optional k_scale / v_scale: (L, P, ps, Hkv) per-row
    dequant sidecars (int8 cache).

    The gather materializes each slot's logical (W*ps) KV prefix — exactly
    the padded-cache traffic the paged kernel's steered page DMAs avoid.
    """
    B, H, d = q.shape
    k, v = _gather_pages(k_pages, v_pages, layer, page_table, d,
                         k_scale, v_scale)
    Hkv, span = k.shape[2], k.shape[1]
    G = H // Hkv
    lengths = lengths.astype(jnp.int32)

    qh = q.astype(jnp.float32).reshape(B, Hkv, G, d)
    s = jnp.einsum("bhgd,bshd->bhgs", qh, k, precision=_F32,
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    kpos = jnp.arange(span)[None, None, None, :]
    # free slots (length 0) attend to position 0 so the softmax stays
    # defined; their rows are zeroed below (matching the kernel's output)
    keep = kpos < jnp.maximum(lengths, 1)[:, None, None, None]
    s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v, precision=_F32,
                   preferred_element_type=jnp.float32)
    o = jnp.where(lengths[:, None, None, None] > 0, o, 0.0)
    return o.reshape(B, H, d).astype(q.dtype)


def paged_prefill_ref(q, k_pages, v_pages, layer, page_table, index, *,
                      k_scale=None, v_scale=None):
    """Multi-query sibling of `paged_decode_ref`: causal attention of S
    query tokens at positions [index, index+S) over a paged KV cache whose
    pages already hold every position <= the query's own (the chunked
    prefill-into-pages path writes the chunk's K/V rows BEFORE attending).

    q: (B, S, H, d); k_pages / v_pages: (L, P, ps, Hkv * d) layer-stacked
    page pools, read at ``layer``; page_table: (B, W) physical page ids;
    index: (B,) each slot's chunk start position.  Optional k_scale /
    v_scale: (L, P, ps, Hkv) per-row dequant sidecars (int8 cache).  Query
    j of slot b attends over cached positions kpos <= index[b] + j — the
    causal mask doubles as the length mask, so stale rows past the chunk
    (recycled pages) are dead by construction.

    Gather-based like the decode oracle: materializes each slot's logical
    (W*ps) KV span once per chunk, which is exactly the prefill traffic a
    steered-page kernel would avoid; the GEMM work (qkv/out projections)
    still rides the MX dispatch in the caller.
    """
    B, S, H, d = q.shape
    k, v = _gather_pages(k_pages, v_pages, layer, page_table, d,
                         k_scale, v_scale)
    Hkv, span = k.shape[2], k.shape[1]
    G = H // Hkv

    qh = q.astype(jnp.float32).reshape(B, S, Hkv, G, d)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qh, k, precision=_F32,
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    qpos = jnp.asarray(index)[:, None] + jnp.arange(S)  # (B, S)
    kpos = jnp.arange(span)
    keep = kpos[None, None, :] <= qpos[:, :, None]      # (B, S, W*ps)
    s = jnp.where(keep[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqs,bshd->bqhgd", p, v, precision=_F32,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, d).astype(q.dtype)
