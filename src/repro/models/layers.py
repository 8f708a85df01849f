"""Transformer building blocks: norms, RoPE, linear, embedding, GQA attention, MLP.

All weight-times-activation contractions route through `repro.core.ops.matmul`
(the MX dispatch), so the paper's kernel serves every architecture.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import ops
from ..core.precision import QuantSpec
from ..kernels.mx_flash_decode import mx_flash_decode, mx_flash_verify
from ..kernels.quant import quantize
from ..kernels.ref import paged_decode_ref, paged_prefill_ref
from .modules import Builder, Module


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 1e4) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Linear / Embedding
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Linear(Module):
    d_in: int
    d_out: int
    axes: Tuple[Optional[str], Optional[str]] = ("embed", "mlp")
    bias: bool = False
    # TP sharding declaration: "allgather" (w column-sharded) or
    # "reduce_scatter" (w row-sharded) — routes through the overlapped ring
    # collective matmul when a collective_policy context is active.
    tp_mode: Optional[str] = None
    # Per-projection precision declaration (core.precision registry name,
    # e.g. "int8" = weights int8 per-tile / activations bf16, or a
    # structured-sparse policy: "sparse24" = 2:4-pruned weights streamed
    # compressed, "sparse24_int8" = the same payload quantized to int8).
    # None/"none" keeps full precision; the ambient use_precision() context
    # still applies when unset.
    precision: Optional[str] = None

    def build(self, mk: Builder):
        p = {"w": mk.param("w", (self.d_in, self.d_out), self.axes)}
        if self.bias:
            p["b"] = mk.param("b", (self.d_out,), (self.axes[1],), init="zeros")
        return p

    def __call__(self, p, x):
        # bias rides the kernel's final-k write-back on the Pallas path
        return ops.linear(x, p["w"], p["b"] if self.bias else None,
                          out_dtype=x.dtype, tp_mode=self.tp_mode,
                          precision=self.precision)


@dataclasses.dataclass(frozen=True)
class Embedding(Module):
    vocab: int
    d: int

    def build(self, mk: Builder):
        return {"table": mk.param("table", (self.vocab, self.d), ("vocab", "embed"), scale=0.02)}

    def __call__(self, p, ids):
        return p["table"][ids]

    def attend(self, p, x):
        """Tied LM head: logits = x @ table^T (f32), through the MX
        dispatch with the table read in its stored (V, D) layout — the
        kernel's NT matmul, or XLA's dot with the transpose folded into its
        dimension numbers; neither makes a (D, V) copy."""
        return ops.matmul(x, p["table"], out_dtype=jnp.float32,
                          b_transposed=True)


# ---------------------------------------------------------------------------
# Attention (GQA) — full, chunked (long-seq), and cached-decode paths
# ---------------------------------------------------------------------------


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*groups, D)."""
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, groups, d)).reshape(
        b, s, h * groups, d
    )


def _paged_kernel(kernel, q, cache, layer, page_table, lengths, interpret):
    """``kernel`` (`mx_flash_decode` / `mx_flash_verify`) over layer
    ``layer`` of the cache's stacked page pools.  On a tensor-parallel mesh
    every device attends with its own heads: query heads (axis -2 of q) and
    the pools' kv-head lanes shard together over the policy axis, so no
    device gathers another's pages."""
    from jax.sharding import PartitionSpec as P

    scales = [cache[k] for k in ("k_scale", "v_scale") if k in cache]

    def call(q, k_pages, v_pages, lay, table, lens, *sc):
        k_scale, v_scale = sc if sc else (None, None)
        return kernel(q, k_pages, v_pages, lay, table, lens, k_scale=k_scale,
                      v_scale=v_scale, interpret=interpret)

    def heads(ax, size):
        if (cache["k_pages"].shape[-1] // q.shape[-1]) % size:
            return None
        qs = P(*[None] * (q.ndim - 2), ax, None)
        lanes = P(None, None, None, ax)
        return ((qs, lanes, lanes, P(), P(), P(), *[lanes] * len(scales)),
                qs)

    return ops.per_device(call, q, cache["k_pages"], cache["v_pages"], layer,
                          page_table, lengths, *scales, specs=heads)


def full_attention(q, k, v, *, causal: bool, q_offset: int = 0) -> jax.Array:
    """q: (B, Sq, H, D), k/v: (B, Sk, H, D).  Materializes (Sq, Sk) scores —
    use only for moderate sequence lengths."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(d)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(sk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def chunked_attention(
    q, k, v, *, causal: bool, block_kv: int = 512, q_offset: int = 0
) -> jax.Array:
    """Flash-style online-softmax attention, scanning over KV blocks.

    The (m, l, o) running statistics are the MX inter-k accumulator pattern on
    the KV axis: partial results stay in the scan carry (registers/VMEM on
    TPU) and HBM sees each KV block exactly once.  Peak memory is
    O(Sq * block_kv) instead of O(Sq * Sk).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nblk = -(-sk // block_kv)
    pad = nblk * block_kv - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, block_kv, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, block_kv, h, d).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(d)
    qpos = jnp.arange(sq)[:, None] + q_offset

    def step(carry, blk):
        m, l, o = carry
        kblk, vblk, idx = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kblk, preferred_element_type=jnp.float32) * scale
        kpos = idx * block_kv + jnp.arange(block_kv)[None, :]
        valid = kpos < sk  # drop right padding
        keep = (qpos >= kpos) & valid if causal else jnp.broadcast_to(valid, (sq, block_kv))
        s = jnp.where(keep[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked blocks: m_new may still be -inf
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        p = jnp.exp(s - m_safe[..., None])  # exp(-inf - finite) == 0 for masked
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vblk, preferred_element_type=jnp.float32
        )
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    # checkpoint each KV step: backward saves only the O(Sq) carries, never
    # the O(Sq x block) score blocks (flash backward's recompute strategy)
    (m, l, o), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, o0), (kb, vb, jnp.arange(nblk))
    )
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class Attention(Module):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 1e4
    causal: bool = True
    chunked_threshold: int = 2048  # switch to online-softmax beyond this
    use_rope: bool = True
    # per-projection precision (qkv/out projections; attention scores stay
    # full precision — the softmax is the numerically fragile part)
    precision: Optional[str] = None

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def build(self, mk: Builder):
        hd = self.hd
        p = {
            "wq": mk.param("wq", (self.d_model, self.n_heads * hd), ("embed", "heads")),
            "wk": mk.param("wk", (self.d_model, self.n_kv_heads * hd), ("embed", "heads")),
            "wv": mk.param("wv", (self.d_model, self.n_kv_heads * hd), ("embed", "heads")),
            "wo": mk.param("wo", (self.n_heads * hd, self.d_model), ("heads", "embed")),
        }
        if self.qkv_bias:
            p["bq"] = mk.param("bq", (self.n_heads * hd,), ("heads",), init="zeros")
            p["bk"] = mk.param("bk", (self.n_kv_heads * hd,), ("heads",), init="zeros")
            p["bv"] = mk.param("bv", (self.n_kv_heads * hd,), ("heads",), init="zeros")
        return p

    def _qkv(self, p, x, positions):
        b, s, _ = x.shape
        hd = self.hd
        bq = p["bq"] if self.qkv_bias else None
        bk = p["bk"] if self.qkv_bias else None
        bv = p["bv"] if self.qkv_bias else None
        # qkv are column-sharded (heads on "model"): under a collective
        # policy they run as ring all-gather ⊗ matmul (sequence chunks
        # stream around the ring while the resident chunk multiplies).
        q = ops.linear(x, p["wq"], bq, out_dtype=x.dtype, tp_mode="allgather",
                       precision=self.precision)
        k = ops.linear(x, p["wk"], bk, out_dtype=x.dtype, tp_mode="allgather",
                       precision=self.precision)
        v = ops.linear(x, p["wv"], bv, out_dtype=x.dtype, tp_mode="allgather",
                       precision=self.precision)
        q = q.reshape(b, s, self.n_heads, hd)
        k = k.reshape(b, s, self.n_kv_heads, hd)
        v = v.reshape(b, s, self.n_kv_heads, hd)
        if self.use_rope:
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)
        return q, k, v

    def __call__(self, p, x, *, positions=None, kv=None, residual=None):
        """Self-attention over x: (B, S, D).  If kv=(k_ext, v_ext) is given,
        attends over those instead (cross-attention; no causal mask).
        `residual` (broadcastable to the output) is fused into the output
        projection's write-back on the Pallas path."""
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        q, k, v = self._qkv(p, x, positions)
        if kv is not None:
            k, v = kv
            causal = False
        else:
            causal = self.causal
        groups = self.n_heads // self.n_kv_heads
        k = _repeat_kv(k, groups) if k.shape[2] != self.n_heads else k
        v = _repeat_kv(v, groups) if v.shape[2] != self.n_heads else v
        if k.shape[1] > self.chunked_threshold:
            o = chunked_attention(q, k, v, causal=causal)
        else:
            o = full_attention(q, k, v, causal=causal)
        o = o.reshape(b, s, self.n_heads * self.hd)
        # wo is row-sharded (heads on the contraction): ring matmul ⊗
        # reduce-scatter — partial sums travel the ring, the residual add
        # fuses into the final ring step's write-back.
        return ops.linear(o, p["wo"], residual=residual, out_dtype=x.dtype,
                          tp_mode="reduce_scatter", precision=self.precision)

    # ---------------- KV-cache decode path ----------------

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        hd = self.hd
        return {
            "k": jnp.zeros((batch, max_len, self.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_len, self.n_kv_heads, hd), dtype),
        }

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        hd = self.hd
        sh = (batch, max_len, self.n_kv_heads, hd)
        return {"k": jax.ShapeDtypeStruct(sh, dtype), "v": jax.ShapeDtypeStruct(sh, dtype)}

    def cache_axes(self):
        ax = ("batch", "cache_seq", "kv_heads", "head_dim")
        return {"k": ax, "v": ax}

    def decode(self, p, x, cache, index, *, residual=None):
        """One decode step.  x: (B, 1, D); cache k/v: (B, Smax, Hkv, hd);
        index: scalar position, or (B,) per-slot positions (continuous
        batching — each slot decodes at its own depth).

        The KV cache's sequence axis is shardable (context-parallel flash
        decoding): softmax statistics reduce over the sharded axis via
        GSPMD-inserted all-reduces — see parallel/sharding.py.
        """
        b = x.shape[0]
        index = jnp.asarray(index)
        idx_b = jnp.broadcast_to(index, (b,))  # per-slot positions
        positions = idx_b[:, None]
        q, k_new, v_new = self._qkv(p, x, positions)
        if index.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), index, axis=1
            )
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), index, axis=1
            )
        else:  # per-slot scatter (continuous batching)
            rows = jnp.arange(b)
            k_cache = cache["k"].at[rows, idx_b].set(
                k_new[:, 0].astype(cache["k"].dtype)
            )
            v_cache = cache["v"].at[rows, idx_b].set(
                v_new[:, 0].astype(cache["v"].dtype)
            )
        groups = self.n_heads // self.n_kv_heads
        k = _repeat_kv(k_cache, groups)
        v = _repeat_kv(v_cache, groups)
        d = self.hd
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        s = s / math.sqrt(d)
        kpos = jnp.arange(k.shape[1])[None, None, None, :]
        s = jnp.where(kpos <= idx_b[:, None, None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
        o = o.reshape(b, 1, self.n_heads * d)
        out = ops.linear(o, p["wo"], residual=residual, out_dtype=x.dtype,
                         tp_mode="reduce_scatter", precision=self.precision)
        return out, {"k": k_cache, "v": v_cache}

    # ---------------- chunked prefill (dense cache) ----------------

    def prefill(self, p, x, cache, index, *, residual=None):
        """Chunked prefill: x (B, S, D) writes cache rows [index, index+S)
        and attends causally against the cache prefix — S prompt tokens per
        launch instead of S decode steps.  `index` is the chunk's start
        position (scalar, shared across the batch)."""
        b, sq, _ = x.shape
        index = jnp.asarray(index)
        positions = jnp.broadcast_to(index + jnp.arange(sq), (b, sq))
        q, k_new, v_new = self._qkv(p, x, positions)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), index, axis=1
        )
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), index, axis=1
        )
        groups = self.n_heads // self.n_kv_heads
        k = _repeat_kv(k_cache, groups)
        v = _repeat_kv(v_cache, groups)
        # causal mask with q_offset == index never reads past the written
        # prefix, so attending over the full cache length is exact
        o = full_attention(q, k, v, causal=True, q_offset=index)
        o = o.reshape(b, sq, self.n_heads * self.hd)
        out = ops.linear(o, p["wo"], residual=residual, out_dtype=x.dtype,
                         tp_mode="reduce_scatter", precision=self.precision)
        return out, {"k": k_cache, "v": v_cache}

    # ---------------- paged KV-cache decode path ----------------

    def _write_kv_pages(self, cache, layer, page_ids, offs, k_new, v_new):
        """Scatter K/V rows into layer ``layer`` of the stacked page pools
        at (page_ids, offs), where the pools lie; the single write path
        shared by decode (one token per slot) and chunked prefill (a chunk
        per slot — the leading dims of page_ids/offs/k_new/v_new just
        broadcast).  k_new / v_new: (..., Hkv, hd), stored as (..., Hkv *
        hd) rows.  A quantized cache (pytree self-describes via its
        "k_scale" key) quantizes on write with a per-(row, head) scale."""
        cache = dict(cache)
        at = (layer, page_ids, offs)

        def as_rows(x):  # (..., Hkv, hd) -> (..., Hkv * hd)
            return x.reshape(*x.shape[:-2], -1)

        if "k_scale" in cache:
            names = {"int8": "int8", "float8_e4m3fn": "fp8_e4m3"}
            spec = QuantSpec(names[str(cache["k_pages"].dtype)], "tile")
            qk, ks = quantize(k_new, spec, axis=-1)
            qv, vs = quantize(v_new, spec, axis=-1)
            cache["k_pages"] = cache["k_pages"].at[at].set(as_rows(qk))
            cache["v_pages"] = cache["v_pages"].at[at].set(as_rows(qv))
            cache["k_scale"] = cache["k_scale"].at[at].set(ks[..., 0])
            cache["v_scale"] = cache["v_scale"].at[at].set(vs[..., 0])
        else:
            dt = cache["k_pages"].dtype
            cache["k_pages"] = cache["k_pages"].at[at].set(
                as_rows(k_new).astype(dt))
            cache["v_pages"] = cache["v_pages"].at[at].set(
                as_rows(v_new).astype(dt))
        return cache

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16, kv_quant: Optional[QuantSpec] = None,
                         layers: int = 1):
        """Layer-stacked page pools: (layers, num_pages, page_size, Hkv *
        hd) per operand, one token's kv heads side by side in a row — the
        layout the decode kernel reads and the writes scatter into, lane-
        dense at the served widths.  `kv_quant` (a quantized
        core.precision.QuantSpec, e.g. QuantSpec("int8")) stores narrow
        payloads plus per-(row, head) f32 scale pools (layers, num_pages,
        page_size, Hkv); the cache pytree self-describes via its `k_scale`
        key."""
        shape = (layers, num_pages, page_size, self.n_kv_heads * self.hd)
        if kv_quant is not None and kv_quant.quantized:
            scales = shape[:3] + (self.n_kv_heads,)
            return {
                "k_pages": jnp.zeros(shape, kv_quant.jnp_dtype),
                "v_pages": jnp.zeros(shape, kv_quant.jnp_dtype),
                "k_scale": jnp.ones(scales, jnp.float32),
                "v_scale": jnp.ones(scales, jnp.float32),
            }
        return {"k_pages": jnp.zeros(shape, dtype),
                "v_pages": jnp.zeros(shape, dtype)}

    def abstract_paged_cache(self, num_pages: int, page_size: int,
                             dtype=jnp.bfloat16,
                             kv_quant: Optional[QuantSpec] = None,
                             layers: int = 1):
        return jax.eval_shape(lambda: self.init_paged_cache(
            num_pages, page_size, dtype, kv_quant, layers))

    def paged_cache_axes(self, kv_quant: Optional[QuantSpec] = None):
        # a row is the token's kv heads side by side: it shards on the
        # model axis by the "kv_heads" rule, whole heads to a device
        ax = ("layers", "pages", "page_size", "kv_heads")
        axes = {"k_pages": ax, "v_pages": ax}
        if kv_quant is not None and kv_quant.quantized:
            axes["k_scale"] = ax
            axes["v_scale"] = ax
        return axes

    def decode_paged(self, p, x, cache, layer, index, page_table, lengths,
                     *, residual=None):
        """One decode step of layer ``layer`` against the layer-stacked
        paged KV cache.  x: (B, 1, D); cache: page pools from
        `init_paged_cache`, written and read where they lie (the model's
        layer scan carries them); index: (B,) per-slot positions;
        page_table: (B, W) physical page ids (runtime/kv_pages — free
        slots' rows point at the dump page, so the batched write needs no
        masking); lengths: (B,) live token counts (index+1 for active
        slots, 0 for free ones).

        The attention itself dispatches like every other MX op: the Pallas
        split-KV kernel (`mx_flash_decode`) under the pallas_mx policy, the
        gather-based jnp oracle (`paged_decode_ref`) as the XLA fallback.
        """
        b = x.shape[0]
        ps = cache["k_pages"].shape[2]
        idx_b = jnp.broadcast_to(jnp.asarray(index), (b,))
        positions = idx_b[:, None]
        q, k_new, v_new = self._qkv(p, x, positions)
        rows = jnp.arange(b)
        page_ids = page_table[rows, idx_b // ps]
        offs = idx_b % ps
        cache = self._write_kv_pages(cache, layer, page_ids, offs,
                                     k_new[:, 0], v_new[:, 0])  # (B, Hkv, hd)
        policy = ops.current_policy()
        if policy.backend == "pallas_mx":
            o = _paged_kernel(mx_flash_decode, q[:, 0], cache, layer,
                              page_table, lengths, policy.interpret)
        else:
            o = paged_decode_ref(q[:, 0], cache["k_pages"], cache["v_pages"],
                                 layer, page_table, lengths,
                                 k_scale=cache.get("k_scale"),
                                 v_scale=cache.get("v_scale"))
        o = o.reshape(b, 1, self.n_heads * self.hd)
        out = ops.linear(o, p["wo"], residual=residual, out_dtype=x.dtype,
                         tp_mode="reduce_scatter", precision=self.precision)
        return out, cache

    # ---------------- chunked prefill (paged cache) ----------------

    def prefill_paged(self, p, x, cache, layer, index, page_table, *,
                      residual=None):
        """Chunked prefill writing K/V DIRECTLY into layer ``layer``'s
        pages: x (B, S, D) fills cache rows for positions [index, index+S)
        — S prompt tokens per launch instead of S decode-interleaved steps
        — then attends causally against the paged prefix (including pages
        mounted from the prefix cache, which is what makes a shared system
        prompt cost zero prefill GEMMs for the matched span).  index: (B,)
        per-slot chunk start positions; page_table: (B, W) physical page
        ids covering at least positions index+S-1.  Quantized caches
        ("k_scale" present) quantize-on-write per row, exactly as
        `decode_paged` does."""
        b, sq, _ = x.shape
        ps = cache["k_pages"].shape[2]
        idx_b = jnp.broadcast_to(jnp.asarray(index), (b,))
        positions = idx_b[:, None] + jnp.arange(sq)  # (B, S)
        q, k_new, v_new = self._qkv(p, x, positions)
        page_ids = jnp.take_along_axis(page_table, positions // ps, axis=1)
        offs = positions % ps
        cache = self._write_kv_pages(cache, layer, page_ids, offs, k_new,
                                     v_new)
        # the attention is the gather oracle on every backend: the split-KV
        # Pallas kernel is single-query (decode); prefill chunks are
        # compute-bound in the qkv/out GEMMs, which already ride MX dispatch
        o = paged_prefill_ref(q, cache["k_pages"], cache["v_pages"], layer,
                              page_table, idx_b,
                              k_scale=cache.get("k_scale"),
                              v_scale=cache.get("v_scale"))
        o = o.reshape(b, sq, self.n_heads * self.hd)
        out = ops.linear(o, p["wo"], residual=residual, out_dtype=x.dtype,
                         tp_mode="reduce_scatter", precision=self.precision)
        return out, cache

    # ---------------- speculative verify (paged cache) ----------------

    def verify_paged(self, p, x, cache, layer, index, page_table, lengths, *,
                     residual=None):
        """Batched-verify step for speculative decoding: x (B, S, D) holds
        each slot's S = k+1 window tokens (the committed token plus k
        drafts).  K/V rows for positions [index, index+S) are written into
        layer ``layer``'s pages first (quantize-on-write included, exactly
        like `prefill_paged`), then all S rows attend in ONE launch through
        `mx_flash_verify` — the decode kernel widened to an S-row query
        block, scoring the whole window for the price of one weight read.

        index: (B,) window start positions; lengths: (B,) live counts
        INCLUDING the window (= index + S for active slots, 0 for free
        ones — free slots' writes land on the dump page and their output
        rows are zero, the decode-path convention)."""
        b, sq, _ = x.shape
        ps = cache["k_pages"].shape[2]
        idx_b = jnp.broadcast_to(jnp.asarray(index), (b,))
        positions = idx_b[:, None] + jnp.arange(sq)  # (B, S)
        q, k_new, v_new = self._qkv(p, x, positions)
        page_ids = jnp.take_along_axis(page_table, positions // ps, axis=1)
        offs = positions % ps
        cache = self._write_kv_pages(cache, layer, page_ids, offs, k_new,
                                     v_new)
        policy = ops.current_policy()
        if policy.backend == "pallas_mx":
            o = _paged_kernel(mx_flash_verify, q, cache, layer, page_table,
                              lengths, policy.interpret)
        else:
            # the causal window mask of the prefill oracle IS the verify
            # mask (row r at position lengths-S+r); free slots (length 0)
            # produce NaN softmax rows there — zero them like the kernel
            o = paged_prefill_ref(q, cache["k_pages"], cache["v_pages"],
                                  layer, page_table, lengths - sq,
                                  k_scale=cache.get("k_scale"),
                                  v_scale=cache.get("v_scale"))
            o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)
        o = o.reshape(b, sq, self.n_heads * self.hd)
        out = ops.linear(o, p["wo"], residual=residual, out_dtype=x.dtype,
                         tp_mode="reduce_scatter", precision=self.precision)
        return out, cache


@dataclasses.dataclass(frozen=True)
class MLP(Module):
    d_model: int
    d_ff: int
    activation: str = "silu"  # "silu" => gated (SwiGLU); "gelu"/"relu" => plain
    # per-projection precision (up/gate/down): quantized ("int8", ...) or
    # structured-sparse ("sparse24", "sparse24_int8") registry names
    precision: Optional[str] = None

    @property
    def gated(self) -> bool:
        return self.activation == "silu"

    def build(self, mk: Builder):
        p = {
            "wi": mk.param("wi", (self.d_model, self.d_ff), ("embed", "mlp")),
            "wo": mk.param("wo", (self.d_ff, self.d_model), ("mlp", "embed")),
        }
        if self.gated:
            p["wg"] = mk.param("wg", (self.d_model, self.d_ff), ("embed", "mlp"))
        return p

    def __call__(self, p, x, *, residual=None):
        """Fused path: silu(x@wg) * (x@wi) is ONE kernel (two accumulators,
        gating at the write-back); the down-projection fuses the residual
        add.  Intermediates never round-trip HBM between matmul and
        consumer."""
        # up/gate are column-sharded -> ring all-gather ⊗ matmul; the down
        # projection is row-sharded -> ring matmul ⊗ reduce-scatter (see
        # kernels/mx_collective_matmul; inert without a collective_policy).
        if self.gated:
            h = ops.linear(x, p["wi"], w_gate=p["wg"], activation="swiglu",
                           out_dtype=x.dtype, tp_mode="allgather",
                           precision=self.precision)
        else:
            act = self.activation if self.activation in ("gelu", "relu") else "relu"
            h = ops.linear(x, p["wi"], activation=act, out_dtype=x.dtype,
                           tp_mode="allgather", precision=self.precision)
        return ops.linear(h, p["wo"], residual=residual, out_dtype=x.dtype,
                          tp_mode="reduce_scatter", precision=self.precision)
