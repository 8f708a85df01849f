"""Model stacks: decoder-only LM (dense/MoE/SSM/hybrid), encoder-decoder, VLM.

Layers are organized in homogeneous *segments*, each scanned with
`jax.lax.scan` over stacked parameters (compile time independent of depth —
essential for 126-layer dry-runs) and rematerialized per block.  Zamba-style
*shared* transformer blocks are applied between segments with tied weights
(the same param tree at every application).

Block kinds: "dense" (attn+MLP), "moe" (attn+MoE), "mamba2", "mlstm",
"slstm", "encdec" (self+cross attn decoder block).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import ops
from ..parallel.sharding import constrain
from .layers import MLP, Attention, Embedding, Linear, rms_norm
from .modules import Builder, Module
from .moe import MoE
from .ssm import Mamba2Block
from .xlstm import MLSTMBlock, SLSTMBlock


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TransformerBlock(Module):
    """Pre-norm attention + MLP/MoE block (decoder unless causal=False)."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 1e4
    causal: bool = True
    use_moe: bool = False
    n_experts: int = 0
    top_k: int = 0
    activation: str = "silu"
    cross_attention: bool = False

    chunk_threshold: int = 2048
    # per-projection precision declaration, threaded into every attention /
    # MLP / MoE projection of this block (core.precision registry name)
    precision: Optional[str] = None

    def _attn(self) -> Attention:
        return Attention(
            self.d_model, self.n_heads, self.n_kv_heads, self.head_dim,
            qkv_bias=self.qkv_bias, rope_theta=self.rope_theta, causal=self.causal,
            chunked_threshold=self.chunk_threshold, precision=self.precision,
        )

    moe_groups: int = 1
    moe_capacity_factor: float = 1.25

    def _ffn(self):
        if self.use_moe:
            return MoE(self.d_model, self.d_ff, self.n_experts, self.top_k,
                       activation=self.activation, n_groups=self.moe_groups,
                       capacity_factor=self.moe_capacity_factor,
                       precision=self.precision)
        return MLP(self.d_model, self.d_ff, activation=self.activation,
                   precision=self.precision)

    def build(self, mk: Builder):
        p = {
            "ln1": mk.param("ln1", (self.d_model,), ("embed",), init="ones"),
            "attn": mk.child("attn", self._attn()),
            "ln2": mk.param("ln2", (self.d_model,), ("embed",), init="ones"),
            "ffn": mk.child("ffn", self._ffn()),
        }
        if self.cross_attention:
            p["ln_x"] = mk.param("ln_x", (self.d_model,), ("embed",), init="ones")
            p["xattn"] = mk.child(
                "xattn",
                Attention(self.d_model, self.n_heads, self.n_kv_heads,
                          self.head_dim, causal=False, use_rope=False),
            )
        return p

    def __call__(self, p, x, *, enc_kv=None):
        attn = self._attn()
        # residual adds fuse into the output-projection write-backs
        x = attn(p["attn"], rms_norm(x, p["ln1"]), residual=x)
        if self.cross_attention:
            assert enc_kv is not None
            xa = self._xattn_module()
            x = xa(p["xattn"], rms_norm(x, p["ln_x"]), kv=enc_kv, residual=x)
        ffn = self._ffn()
        aux = jnp.float32(0.0)
        h = rms_norm(x, p["ln2"])
        if self.use_moe:
            y, aux = ffn(p["ffn"], h)
            x = x + y
        else:
            x = ffn(p["ffn"], h, residual=x)
        return x, aux

    def _xattn_module(self):
        return Attention(self.d_model, self.n_heads, self.n_kv_heads,
                         self.head_dim, causal=False, use_rope=False)

    # ---- decode ----

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self._attn().init_cache(batch, max_len, dtype)

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self._attn().abstract_cache(batch, max_len, dtype)

    def cache_axes(self):
        return self._attn().cache_axes()

    def decode(self, p, x, cache, index, *, enc_kv=None):
        attn = self._attn()
        x, cache = attn.decode(p["attn"], rms_norm(x, p["ln1"]), cache, index,
                               residual=x)
        if self.cross_attention:
            xa = self._xattn_module()
            x = xa(p["xattn"], rms_norm(x, p["ln_x"]), kv=enc_kv, residual=x)
        ffn = self._ffn()
        h = rms_norm(x, p["ln2"])
        if self.use_moe:
            y, _ = ffn(p["ffn"], h)
            x = x + y
        else:
            x = ffn(p["ffn"], h, residual=x)
        return x, cache

    def prefill(self, p, x, cache, index):
        """Multi-token cache-writing step (chunked prefill; no cross-attn)."""
        attn = self._attn()
        x, cache = attn.prefill(p["attn"], rms_norm(x, p["ln1"]), cache, index,
                                residual=x)
        ffn = self._ffn()
        h = rms_norm(x, p["ln2"])
        if self.use_moe:
            y, _ = ffn(p["ffn"], h)
            x = x + y
        else:
            x = ffn(p["ffn"], h, residual=x)
        return x, cache

    # ---- paged decode ----

    def init_paged_cache(self, num_pages, page_size, dtype=jnp.bfloat16,
                         kv_quant=None, layers=1):
        return self._attn().init_paged_cache(num_pages, page_size, dtype,
                                             kv_quant, layers)

    def abstract_paged_cache(self, num_pages, page_size, dtype=jnp.bfloat16,
                             kv_quant=None, layers=1):
        return self._attn().abstract_paged_cache(num_pages, page_size, dtype,
                                                 kv_quant, layers)

    def paged_cache_axes(self, kv_quant=None):
        return self._attn().paged_cache_axes(kv_quant)

    def paged(self, step: str, p, x, cache, *args):
        """One layer of a paged program: the attention's ``step``
        (`Attention.decode_paged`, `prefill_paged` or `verify_paged`, which
        writes the layer's K/V rows into the stacked pools) with the
        residual fused, then the FFN."""
        x, cache = getattr(self._attn(), step)(
            p["attn"], rms_norm(x, p["ln1"]), cache, *args, residual=x)
        ffn = self._ffn()
        h = rms_norm(x, p["ln2"])
        if self.use_moe:
            y, _ = ffn(p["ffn"], h)
            x = x + y
        else:
            x = ffn(p["ffn"], h, residual=x)
        return x, cache


def _wrap_state_block(block):
    """Uniform (y, aux) interface for state blocks (mamba/xlstm)."""

    class _W:
        def __call__(self, p, x, **kw):
            return block(p, x), jnp.float32(0.0)

    return _W()


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str
    n: int


def make_block(kind: str, cfg) -> Module:
    """cfg is an ArchConfig (configs/base.py)."""
    prec = getattr(cfg, "precision", None)
    if kind in ("dense", "moe"):
        return TransformerBlock(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            head_dim=cfg.head_dim, qkv_bias=cfg.qkv_bias,
            rope_theta=cfg.rope_theta, use_moe=(kind == "moe"),
            n_experts=cfg.n_experts, top_k=cfg.top_k, activation=cfg.activation,
            chunk_threshold=cfg.attn_chunk_threshold, moe_groups=cfg.moe_groups,
            moe_capacity_factor=cfg.moe_capacity_factor, precision=prec,
        )
    if kind == "encdec":
        return TransformerBlock(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            activation=cfg.activation, cross_attention=True,
            chunk_threshold=cfg.attn_chunk_threshold, precision=prec,
        )
    if kind == "mamba2":
        return Mamba2Block(cfg.d_model, d_state=cfg.ssm_state, chunk=cfg.ssm_chunk)
    if kind == "mlstm":
        return MLSTMBlock(cfg.d_model, cfg.n_heads)
    if kind == "slstm":
        return SLSTMBlock(cfg.d_model, cfg.n_heads)
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# Decoder-only LM
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecoderLM(Module):
    """Decoder LM over segments, with optional Zamba-style shared block and
    optional modality-frontend projector (VLM/audio prefix embeddings)."""

    cfg: Any  # ArchConfig

    def segments(self) -> Tuple[Segment, ...]:
        return tuple(Segment(k, n) for k, n in self.cfg.blocks)

    def build(self, mk: Builder):
        cfg = self.cfg
        p = {"embed": mk.child("embed", Embedding(cfg.vocab, cfg.d_model))}
        for i, seg in enumerate(self.segments()):
            p[f"seg{i}"] = mk.stacked(f"seg{i}", make_block(seg.kind, cfg), seg.n)
        if cfg.shared_attn_every:
            p["shared"] = mk.child(
                "shared",
                TransformerBlock(
                    cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff or 4 * cfg.d_model,
                    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                ),
            )
        if cfg.frontend_dim:
            p["frontend_proj"] = mk.child(
                "frontend_proj",
                Linear(cfg.frontend_dim, cfg.d_model, axes=(None, "embed")),
            )
        p["ln_f"] = mk.param("ln_f", (cfg.d_model,), ("embed",), init="ones")
        if not cfg.tie_embeddings:
            p["lm_head"] = mk.param(
                "lm_head", (cfg.d_model, cfg.vocab), ("embed", "vocab")
            )
        return p

    # -- helpers --

    def _shared_points(self, seg_idx: int, layer_idx_in_seg: int) -> bool:
        return False  # shared block applied between sub-segments; see _run_segment

    def _embed_inputs(self, p, tokens, prefix_embeds=None):
        x = Embedding(self.cfg.vocab, self.cfg.d_model)(p["embed"], tokens)
        if prefix_embeds is not None:
            proj = Linear(self.cfg.frontend_dim, self.cfg.d_model, axes=(None, "embed"))
            pre = proj(p["frontend_proj"], prefix_embeds.astype(x.dtype))
            x = jnp.concatenate([pre, x], axis=1)
        return x

    def _run_segment(self, seg: Segment, seg_params, x, shared_params, *, remat=True):
        """Scan a homogeneous segment; apply the shared block every
        `shared_attn_every` layers (tied weights) if configured."""
        cfg = self.cfg
        block = make_block(seg.kind, cfg)
        every = cfg.shared_attn_every

        def body(carry, layer_params):
            h, aux = carry
            h = constrain(h, ("batch", "seq", "embed"))
            if seg.kind in ("dense", "moe", "encdec"):
                y, a = block(layer_params, h)
            else:
                y = block(layer_params, h)
                a = jnp.float32(0.0)
            y = constrain(y, ("batch", "seq", "embed"))
            return (y, aux + a), None

        policy = getattr(cfg, "remat_policy", "full")
        if not remat or policy == "none":
            body_fn = body
        elif policy == "dots":
            body_fn = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )
        else:
            body_fn = jax.checkpoint(body)

        if not every:
            (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), seg_params)
            return x, aux

        # shared-block interleaving: scan in groups of `every`
        n_groups = seg.n // every
        aux = jnp.float32(0.0)
        shared_block = TransformerBlock(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff or 4 * cfg.d_model,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        )
        grouped = jax.tree.map(
            lambda t: t.reshape(n_groups, every, *t.shape[1:]), seg_params
        )
        for g in range(n_groups):
            part = jax.tree.map(lambda t: t[g], grouped)
            (x, aux), _ = jax.lax.scan(body_fn, (x, aux), part)
            y, a = shared_block(shared_params, x)  # tied weights every time
            x, aux = y, aux + a
        return x, aux

    def _head(self, p, x):
        """Shared stack epilogue: final norm + LM head -> f32 logits.
        lm_head is vocab(column)-sharded: ring all-gather ⊗ matmul under a
        collective policy, plain MX dispatch otherwise; tied embeddings use
        the transpose-folded jnp.dot (Embedding.attend)."""
        cfg = self.cfg
        x = rms_norm(x, p["ln_f"])
        if cfg.tie_embeddings:
            return Embedding(cfg.vocab, cfg.d_model).attend(p["embed"], x)
        return ops.linear(x, p["lm_head"], out_dtype=jnp.float32,
                          tp_mode="allgather")

    def __call__(self, p, tokens, *, prefix_embeds=None):
        """tokens: (B, S) -> logits (B, S_total, vocab) f32, aux loss."""
        x = self._embed_inputs(p, tokens, prefix_embeds)
        aux = jnp.float32(0.0)
        for i, seg in enumerate(self.segments()):
            x, a = self._run_segment(seg, p[f"seg{i}"], x, p.get("shared"))
            aux = aux + a
        return self._head(p, x), aux

    # ---------------- decode ----------------

    def _seg_block_cache(self, seg: Segment, batch, max_len, mode, dtype=jnp.bfloat16):
        block = make_block(seg.kind, self.cfg)
        if seg.kind in ("dense", "moe", "encdec"):
            fn = {"init": block.init_cache, "abstract": block.abstract_cache,
                  "axes": lambda *a, **k: block.cache_axes()}[mode]
            return fn(batch, max_len, dtype) if mode != "axes" else block.cache_axes()
        fn = {"init": block.init_state, "abstract": block.abstract_state,
              "axes": lambda *a, **k: block.state_axes()}[mode]
        return fn(batch) if mode != "axes" else block.state_axes()

    def _stack_cache(self, one, n, mode):
        if mode == "axes":
            return jax.tree.map(
                lambda ax: (None,) + ax, one, is_leaf=lambda x: isinstance(x, tuple)
            )
        if mode == "abstract":
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), one
            )
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    def make_cache(self, batch: int, max_len: int, mode: str = "init",
                   dtype=jnp.bfloat16):
        """Cache pytree: {"seg{i}": stacked cache, "shared": per-application}."""
        cfg = self.cfg
        cache = {}
        for i, seg in enumerate(self.segments()):
            one = self._seg_block_cache(seg, batch, max_len, mode, dtype)
            cache[f"seg{i}"] = self._stack_cache(one, seg.n, mode)
            if cfg.shared_attn_every and seg.kind == "mamba2":
                napp = seg.n // cfg.shared_attn_every
                shared_block = TransformerBlock(
                    cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.d_ff or 4 * cfg.d_model, head_dim=cfg.head_dim)
                if mode == "axes":
                    one_s = shared_block.cache_axes()
                elif mode == "abstract":
                    one_s = shared_block.abstract_cache(batch, max_len, dtype)
                else:
                    one_s = shared_block.init_cache(batch, max_len, dtype)
                cache[f"shared{i}"] = self._stack_cache(one_s, napp, mode)
        return cache

    # ---- paged decode / chunked prefill capability ----

    def _attn_only(self) -> bool:
        """All segments are KV-cache attention blocks with no shared-block
        interleaving and no modality prefix — the shapes the paged decode
        and chunked-prefill paths cover (state/shared/prefix models keep
        the dense paths)."""
        cfg = self.cfg
        return (not cfg.shared_attn_every and not cfg.frontend_dim
                and all(kind in ("dense", "moe") for kind, _ in cfg.blocks))

    def supports_paged(self) -> bool:
        return self._attn_only()

    def supports_chunked_prefill(self) -> bool:
        return self._attn_only()

    def make_paged_cache(self, num_pages: int, page_size: int,
                         mode: str = "init", dtype=jnp.bfloat16,
                         kv_quant=None):
        """Paged cache pytree: per segment, one layer-stacked pool per
        operand, (n, num_pages, page_size, Hkv * hd).  The step carries the
        pools through its layer scan and writes them where they lie, so a
        donated step updates them in place.  The page table and lengths are
        NOT part of the cache — they are per-step scheduler outputs
        (runtime/kv_pages) shared by every layer."""
        if not self.supports_paged():
            raise ValueError(f"{self.cfg.name}: paged decode needs attention-"
                             "only segments (no shared block / prefix)")
        cache = {}
        for i, seg in enumerate(self.segments()):
            block = make_block(seg.kind, self.cfg)
            if mode == "axes":
                cache[f"seg{i}"] = block.paged_cache_axes(kv_quant)
                continue
            make = (block.abstract_paged_cache if mode == "abstract"
                    else block.init_paged_cache)
            cache[f"seg{i}"] = make(num_pages, page_size, dtype, kv_quant,
                                    seg.n)
        return cache

    def _scan_paged(self, step, p, tokens, cache, *args):
        """The stack's paged pass: `TransformerBlock.paged` over ``step``
        (an `Attention` paged method) a layer at a time.  The pools ride
        the scan's carry, not its xs/ys: each layer writes its rows at its
        own index of the stacked pools, and nothing slices a layer out or
        stacks it back."""
        x = self._embed_inputs(p, tokens)
        new_cache = dict(cache)
        for i, seg in enumerate(self.segments()):
            block = make_block(seg.kind, self.cfg)

            def body(carry, scanned, block=block):
                h, pools = carry
                layer_params, layer = scanned
                return block.paged(step, layer_params, h, pools, layer,
                                   *args), None

            (x, new_cache[f"seg{i}"]), _ = jax.lax.scan(
                body, (x, cache[f"seg{i}"]),
                (p[f"seg{i}"], jnp.arange(seg.n, dtype=jnp.int32)))
        return self._head(p, x), new_cache

    def decode_step_paged(self, p, token, cache, index, page_table, lengths):
        """One token for the whole stack against the paged KV cache.
        token: (B, 1); index: (B,) per-slot positions; page_table: (B, W)
        physical page ids; lengths: (B,) live token counts.
        Returns (logits, cache)."""
        return self._scan_paged("decode_paged", p, token, cache, index,
                                page_table, lengths)

    def prefill_step_paged(self, p, tokens, cache, index, page_table):
        """S prompt tokens through the whole stack in ONE step, written
        DIRECTLY into the paged cache's pages — the paged analogue of
        `prefill_step`.  tokens: (B, S); index: (B,) per-slot chunk start
        positions; page_table: (B, W) physical page ids.  Returns
        (logits, cache); a prefix-cache miss costs O(prompt/chunk) such
        launches instead of O(prompt) decode-interleaved steps."""
        if not self.supports_paged():
            raise ValueError(f"{self.cfg.name}: paged prefill needs "
                             "attention-only segments")
        return self._scan_paged("prefill_paged", p, tokens, cache, index,
                                page_table)

    def verify_step_paged(self, p, tokens, cache, index, page_table, lengths):
        """Score S = k+1 window tokens per slot through the whole stack in
        ONE launch — the speculative-decoding verify pass.  tokens: (B, S)
        (each slot's committed token followed by its k draft tokens);
        index: (B,) window start positions; lengths: (B,) live counts
        including the window.  Returns (logits (B, S, vocab), cache):
        logits[:, r] scores position index+r, so logits[:, r].argmax() is
        the greedy token AFTER accepting rows 0..r — row 0 reproduces the
        plain decode step's output bitwise (k=0 degenerate), rows 1..k are
        the k extra tokens this launch buys."""
        if not self.supports_paged():
            raise ValueError(f"{self.cfg.name}: speculative verify needs "
                             "attention-only segments")
        return self._scan_paged("verify_paged", p, tokens, cache, index,
                                page_table, lengths)

    # ---- chunked prefill ----

    def prefill_step(self, p, tokens, cache, index):
        """S prompt tokens through the whole stack in ONE step, writing
        cache rows [index, index+S).  tokens: (B, S) -> (logits, cache);
        time-to-first-token becomes O(prompt_len / chunk) launches instead
        of O(prompt_len) decode steps."""
        if not self.supports_chunked_prefill():
            raise ValueError(f"{self.cfg.name}: chunked prefill needs "
                             "attention-only segments")
        cfg = self.cfg
        x = self._embed_inputs(p, tokens)
        new_cache = dict(cache)
        for i, seg in enumerate(self.segments()):
            block = make_block(seg.kind, cfg)

            def body(h, scanned):
                layer_params, layer_cache = scanned
                return block.prefill(layer_params, h, layer_cache, index)

            x, new_cache[f"seg{i}"] = jax.lax.scan(
                body, x, (p[f"seg{i}"], cache[f"seg{i}"])
            )
        return self._head(p, x), new_cache

    def decode_step(self, p, token, cache, index, *, prefix_embeds=None):
        """One token for the whole stack.  token: (B, 1) -> (logits, cache)."""
        cfg = self.cfg
        x = self._embed_inputs(p, token, prefix_embeds)
        new_cache = dict(cache)
        for i, seg in enumerate(self.segments()):
            block = make_block(seg.kind, cfg)
            every = cfg.shared_attn_every

            def body(h, scanned):
                layer_params, layer_cache = scanned
                if seg.kind in ("dense", "moe", "encdec"):
                    y, c = block.decode(layer_params, h, layer_cache, index)
                else:
                    y, c = block.decode(layer_params, h, layer_cache)
                return y, c

            if not every:
                x, new_cache[f"seg{i}"] = jax.lax.scan(
                    body, x, (p[f"seg{i}"], cache[f"seg{i}"])
                )
            else:
                n_groups = seg.n // every
                grouped_p = jax.tree.map(
                    lambda t: t.reshape(n_groups, every, *t.shape[1:]), p[f"seg{i}"]
                )
                grouped_c = jax.tree.map(
                    lambda t: t.reshape(n_groups, every, *t.shape[1:]),
                    cache[f"seg{i}"],
                )
                shared_block = TransformerBlock(
                    cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.d_ff or 4 * cfg.d_model, head_dim=cfg.head_dim)
                new_gc, new_sc = [], []
                for g in range(n_groups):
                    part_p = jax.tree.map(lambda t: t[g], grouped_p)
                    part_c = jax.tree.map(lambda t: t[g], grouped_c)
                    x, c = jax.lax.scan(body, x, (part_p, part_c))
                    new_gc.append(c)
                    sc = jax.tree.map(lambda t: t[g], cache[f"shared{i}"])
                    x, sc = shared_block.decode(p["shared"], x, sc, index)
                    new_sc.append(sc)
                new_cache[f"seg{i}"] = jax.tree.map(
                    lambda *ts: jnp.stack(ts).reshape(seg.n, *ts[0].shape[1:]), *new_gc
                )
                new_cache[f"shared{i}"] = jax.tree.map(
                    lambda *ts: jnp.stack(ts), *new_sc
                )
        return self._head(p, x), new_cache


# ---------------------------------------------------------------------------
# Encoder-decoder (seamless-m4t style backbone; frontend is a stub)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EncDecLM(Module):
    cfg: Any

    def build(self, mk: Builder):
        cfg = self.cfg
        enc_block = TransformerBlock(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            head_dim=cfg.head_dim, causal=False, activation=cfg.activation,
        )
        dec_block = make_block("encdec", cfg)
        return {
            "frontend_proj": mk.child(
                "frontend_proj", Linear(cfg.frontend_dim, cfg.d_model, axes=(None, "embed"))
            ),
            "embed": mk.child("embed", Embedding(cfg.vocab, cfg.d_model)),
            "enc": mk.stacked("enc", enc_block, cfg.enc_layers),
            "enc_ln": mk.param("enc_ln", (cfg.d_model,), ("embed",), init="ones"),
            "dec": mk.stacked("dec", dec_block, cfg.n_layers),
            "ln_f": mk.param("ln_f", (cfg.d_model,), ("embed",), init="ones"),
        }

    def encode(self, p, frames):
        """frames: (B, S_enc, frontend_dim) precomputed modality embeddings."""
        cfg = self.cfg
        proj = Linear(cfg.frontend_dim, cfg.d_model, axes=(None, "embed"))
        x = proj(p["frontend_proj"], frames)
        enc_block = TransformerBlock(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            head_dim=cfg.head_dim, causal=False, activation=cfg.activation,
        )

        def body(carry, layer_params):
            h, aux = carry
            y, a = enc_block(layer_params, h)
            return (y, aux + a), None

        (x, _), _ = jax.lax.scan(jax.checkpoint(body), (x, jnp.float32(0.0)), p["enc"])
        return rms_norm(x, p["enc_ln"])

    def _enc_kv(self, p_layer, enc_out, block):
        """Per-decoder-layer cross K/V from encoder output."""
        b, s, _ = enc_out.shape
        hd = block.head_dim or block.d_model // block.n_heads
        att = p_layer["xattn"]
        k = ops.matmul(enc_out, att["wk"], out_dtype=enc_out.dtype)
        v = ops.matmul(enc_out, att["wv"], out_dtype=enc_out.dtype)
        k = k.reshape(b, s, block.n_kv_heads, hd)
        v = v.reshape(b, s, block.n_kv_heads, hd)
        from .layers import _repeat_kv

        g = block.n_heads // block.n_kv_heads
        return _repeat_kv(k, g), _repeat_kv(v, g)

    def __call__(self, p, frames, tokens):
        """Returns decoder logits (B, S_dec, vocab), aux."""
        cfg = self.cfg
        enc_out = self.encode(p, frames)
        x = Embedding(cfg.vocab, cfg.d_model)(p["embed"], tokens)
        block = make_block("encdec", cfg)

        def body(carry, layer_params):
            h, aux = carry
            enc_kv = self._enc_kv(layer_params, enc_out, block)
            y, a = block(layer_params, h, enc_kv=enc_kv)
            return (y, aux + a), None

        (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, jnp.float32(0.0)), p["dec"])
        x = rms_norm(x, p["ln_f"])
        logits = Embedding(cfg.vocab, cfg.d_model).attend(p["embed"], x)
        return logits, aux

    def make_cache(self, batch, max_len, mode="init", dtype=jnp.bfloat16):
        block = make_block("encdec", self.cfg)
        if mode == "axes":
            one = block.cache_axes()
            return {"dec": jax.tree.map(lambda ax: (None,) + ax, one,
                                        is_leaf=lambda x: isinstance(x, tuple))}
        one = (block.abstract_cache if mode == "abstract" else block.init_cache)(
            batch, max_len, dtype
        )
        if mode == "abstract":
            stk = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((self.cfg.n_layers,) + s.shape, s.dtype), one
            )
        else:
            stk = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.cfg.n_layers,) + a.shape), one
            )
        return {"dec": stk}

    def decode_step(self, p, token, cache, index, *, enc_out):
        cfg = self.cfg
        x = Embedding(cfg.vocab, cfg.d_model)(p["embed"], token)
        block = make_block("encdec", cfg)

        def body(h, scanned):
            layer_params, layer_cache = scanned
            enc_kv = self._enc_kv(layer_params, enc_out, block)
            y, c = block.decode(layer_params, h, layer_cache, index, enc_kv=enc_kv)
            return y, c

        x, new_dec = jax.lax.scan(body, x, (p["dec"], cache["dec"]))
        x = rms_norm(x, p["ln_f"])
        logits = Embedding(cfg.vocab, cfg.d_model).attend(p["embed"], x)
        return logits, {"dec": new_dec}
