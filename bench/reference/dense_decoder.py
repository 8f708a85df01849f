"""Plain reference for a dense pre-norm decoder LM (the Llama/Qwen2 block).

Written from the published description, in straightforward jax.numpy and
float32, with no kernels, cache or batching of the system under test, and
nothing imported from it:

    h = embed[tokens]
    per layer:  a = rms_norm(h) * ln1
                q, k, v = a @ wq (+ bq), a @ wk (+ bk), a @ wv (+ bv)
                rotary embedding on q and k (rotate-half form, base theta)
                o = causal softmax(q k^T / sqrt(hd)) v, with grouped kv heads
                h = h + o @ wo
                m = rms_norm(h) * ln2
                h = h + (silu(m @ wg) * (m @ wi)) @ wo_ffn
    logits = (rms_norm(h) * ln_f) @ head   (head = embed^T when tied)

`layout()` names every weight, its shape and how the benchmark draws it
from the seed; the names are the serving program's parameter tree, so the
same draw feeds the program and this reference.  `dot` is the contraction
used everywhere: exact float32 (`highest`) for the reference, three bf16
passes (`high`) for the precision control, one (`bf16`) for a reading
below it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6  # rms_norm epsilon of both published configs


def layout(c: dict) -> dict:
    """{path tuple: (shape, kind, scale)}; kind is "normal" (scale * N(0,1)),
    "norm" (1 + scale * N(0,1)) or "bias" (scale * N(0,1)).  Leaves under
    "seg0" carry the layer axis first and are drawn layer by layer."""
    d, L, ff, V = c["hidden_size"], c["num_hidden_layers"], \
        c["intermediate_size"], c["vocab_size"]
    H, Hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    out = {
        ("embed", "table"): ((V, d), "normal", 0.02),
        ("seg0", "ln1"): ((L, d), "norm", 0.1),
        ("seg0", "ln2"): ((L, d), "norm", 0.1),
        ("seg0", "attn", "wq"): ((L, d, H * hd), "normal", d ** -0.5),
        ("seg0", "attn", "wk"): ((L, d, Hkv * hd), "normal", d ** -0.5),
        ("seg0", "attn", "wv"): ((L, d, Hkv * hd), "normal", d ** -0.5),
        ("seg0", "attn", "wo"): ((L, H * hd, d), "normal", (H * hd) ** -0.5),
        ("seg0", "ffn", "wi"): ((L, d, ff), "normal", d ** -0.5),
        ("seg0", "ffn", "wg"): ((L, d, ff), "normal", d ** -0.5),
        ("seg0", "ffn", "wo"): ((L, ff, d), "normal", ff ** -0.5),
        ("ln_f",): ((d,), "norm", 0.1),
    }
    if c["attention_bias"]:
        out[("seg0", "attn", "bq")] = ((L, H * hd), "bias", 0.1)
        out[("seg0", "attn", "bk")] = ((L, Hkv * hd), "bias", 0.1)
        out[("seg0", "attn", "bv")] = ((L, Hkv * hd), "bias", 0.1)
    if not c["tie_word_embeddings"]:
        out[("lm_head",)] = ((d, V), "normal", d ** -0.5)
    return out


def dot_highest(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def dot_high(eq, a, b):
    """float32 contraction in three bf16 passes (what `high` means on a
    TPU): hi*hi + hi*lo + lo*hi, the lo*lo term dropped.  The high part is
    rounded by `reduce_precision`, not by a round trip through bfloat16,
    which XLA may fold away (excess precision) and so leave lo at zero."""
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def f(x, y):
        return jnp.einsum(eq, x, y, preferred_element_type=jnp.float32)

    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def dot_bf16(eq, a, b):
    """One bf16 pass with a float32 sum (`default` on a TPU)."""
    return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


DOTS = {"highest": dot_highest, "high": dot_high, "bf16": dot_bf16}


def rms_norm(x, w):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * w


def rope(x, theta):
    """x: (S, T, heads, hd), rotate-half form over positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(c: dict, dot, w: dict, h, q_block: int = 512):
    """One block over h: (S, T, d) padded sequences.  Causal attention runs
    in blocks of query rows so that the scores fit; positions past a
    sequence's end never influence earlier ones."""
    S, T, _ = h.shape
    H, Hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    a = rms_norm(h, w["ln1"])

    def proj(name, bias, heads):
        y = dot("std,de->ste", a, w[name])
        if bias in w:
            y = y + w[bias]
        return y.reshape(S, T, heads, hd)

    theta = c["rope_theta"]
    q = rope(proj("wq", "bq", H), theta)
    k = rope(proj("wk", "bk", Hkv), theta)
    v = proj("wv", "bv", Hkv)
    g = H // Hkv
    q = q.reshape(S, T, Hkv, g, hd)
    outs = []
    for r0 in range(0, T, q_block):
        qb = q[:, r0:r0 + q_block]
        s = dot("sqkgd,stkd->skgqt", qb, k) / math.sqrt(hd)
        qpos = r0 + jnp.arange(qb.shape[1])
        s = jnp.where(qpos[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(dot("skgqt,stkd->sqkgd", p, v))
    o = jnp.concatenate(outs, axis=1).reshape(S, T, H * hd)
    h = h + dot("ste,ed->std", o, w["wo"])
    m = rms_norm(h, w["ln2"])
    gate = dot("std,df->stf", m, w["wg"])
    up = dot("std,df->stf", m, w["wi"])
    return h + dot("stf,fd->std", jax.nn.silu(gate) * up, w["wo_ffn"])


def layer_weights(params: dict, i: int) -> dict:
    """Layer i's weights under the names `layer` reads."""
    seg = params["seg0"]
    w = {"ln1": seg["ln1"][i], "ln2": seg["ln2"][i],
         "wq": seg["attn"]["wq"][i], "wk": seg["attn"]["wk"][i],
         "wv": seg["attn"]["wv"][i], "wo": seg["attn"]["wo"][i],
         "wi": seg["ffn"]["wi"][i], "wg": seg["ffn"]["wg"][i],
         "wo_ffn": seg["ffn"]["wo"][i]}
    for b in ("bq", "bk", "bv"):
        if b in seg["attn"]:
            w[b] = seg["attn"][b][i]
    return w


def head_matrix(c: dict, params: dict):
    """(d, V) output projection."""
    if c["tie_word_embeddings"]:
        return params["embed"]["table"].T
    return params["lm_head"]


def head_readout(c: dict, dot, ln_f, head, h, pos, served, block: int = 64):
    """Logit statistics at the positions that predict served tokens.

    h: (S, T, d) final hidden states; pos: (S, P) positions (padding rows
    repeat a valid one); served: (S, P) the token served there.  Returns
    (best, at_served, argmax), each (S, P): the largest logit, the served
    token's logit and the index of the largest, computed in blocks of
    positions so that (S, block, V) logits fit."""
    x = jnp.take_along_axis(h, pos[..., None], axis=1)
    x = rms_norm(x, ln_f)
    S, P, d = x.shape
    nb = -(-P // block)
    pad = nb * block - P
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    tok = jnp.pad(served, ((0, 0), (0, pad)))
    xb = x.reshape(S, nb, block, d).transpose(1, 0, 2, 3)
    tb = tok.reshape(S, nb, block).transpose(1, 0, 2)

    def one(args):
        xs, ts = args
        logits = dot("spd,dv->spv", xs, head)
        at = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
        return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)

    best, at, am = jax.lax.map(one, (xb, tb))

    def back(a):
        return a.transpose(1, 0, 2).reshape(S, nb * block)[:, :P]

    return back(best), back(at), back(am)
