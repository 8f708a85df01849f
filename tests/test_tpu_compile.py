"""The main path's Pallas kernels compile for a TPU v5e at llama3.2-1b's
published widths, at the plans the dispatch picks itself, and the decode
attention and whole decode step at qwen2-0.5b's chat-cell shapes.

Nothing here runs: each test lowers and compiles for a *described* chip
(`jax.experimental.topologies`), which raises what the chip's compiler
would raise — a block shape the TPU lowering refuses, more VMEM than the
kernel's scoped limit.  Interpret-mode tests cannot see either.  The
topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the worker running this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.core import ops
from repro.core.hlo_census import kernel_launches
from repro.kernels.mx_flash_decode import mx_flash_decode, mx_flash_verify

CFG = get_config("llama3.2-1b")
D, HD = CFG.d_model, CFG.hd
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables can be written to the persistent
    # cache but never read back here: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pol():
    """The serving dispatch on a TPU: the MX kernels, compiled, at the
    planner's own tiles."""
    return ops.MXPolicy(backend="pallas_mx", interpret=False)


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; returns its kernel census."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    return kernel_launches(compiled.as_text())


# (name, K, N, epilogue) — every projection of one llama3.2-1b layer and
# the tied LM head, with the epilogue the model fuses into it
GEMMS = [
    ("q", D, CFG.n_heads * HD, "none"),
    ("kv", D, CFG.n_kv_heads * HD, "none"),
    ("out", CFG.n_heads * HD, D, "residual"),
    ("up_gate", D, CFG.d_ff, "swiglu"),
    ("down", CFG.d_ff, D, "residual"),
    ("lm_head", D, CFG.vocab, "tied"),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("M", [8, 512])
@pytest.mark.parametrize("name,K,N,ep", GEMMS, ids=[g[0] for g in GEMMS])
def test_fused_gemm_compiles(one_chip, pol, name, K, N, ep, M, dtype):
    def S(shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if ep == "tied":  # table stored (vocab, d_model), read transposed
        def fn(x, w):
            return ops.matmul(x, w, policy=pol, out_dtype=jnp.float32,
                              b_transposed=True)
        census = _compile(fn, S((M, K)), S((N, K)))
    elif ep == "swiglu":
        def fn(x, w, g):
            return ops.linear(x, w, w_gate=g, activation="swiglu",
                              policy=pol)
        census = _compile(fn, S((M, K)), S((K, N)), S((K, N)))
    elif ep == "residual":
        def fn(x, w, r):
            return ops.linear(x, w, residual=r, policy=pol)
        census = _compile(fn, S((M, K)), S((K, N)), S((M, N)))
    else:
        def fn(x, w):
            return ops.linear(x, w, policy=pol)
        census = _compile(fn, S((M, K)), S((K, N)))
    assert census == {"mx_matmul_fused": 1}


def test_int8_weight_gemm_compiles(one_chip, pol):
    """bf16 activations against int8 per-column weights (the "int8"
    precision policy) on the SwiGLU up/gate projection."""
    x = jax.ShapeDtypeStruct((512, D), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D, CFG.d_ff), jnp.bfloat16, sharding=one_chip)

    def fn(x, w, g):
        return ops.linear(x, w, w_gate=g, activation="swiglu", policy=pol,
                          precision="int8")
    assert _compile(fn, x, w, w)["mx_matmul_fused"] == 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("S", [1, 5], ids=["decode", "verify_s5"])
def test_paged_attention_compiles(one_chip, S, dtype):
    """Page 16, Hkv 8, G 4, d 64: the decode kernel, and the verify kernel
    at a 5-row speculative window, over a 34-page table."""
    B, W, pages = 8, 34, 8 * 34 + 1

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((B, CFG.n_heads, HD) if S == 1 else (B, S, CFG.n_heads, HD))
    kv = sds((pages, PAGE, CFG.n_kv_heads, HD))
    table, lengths = sds((B, W), jnp.int32), sds((B,), jnp.int32)
    kernel = mx_flash_decode if S == 1 else mx_flash_verify
    census = _compile(lambda *a: kernel(*a), q, kv, kv, table, lengths)
    assert census == {kernel.__name__: 1}


def test_int8_paged_decode_compiles(one_chip):
    """int8 KV pages with their per-row f32 scale pages."""
    B, W, pages = 8, 34, 8 * 34 + 1
    q = jax.ShapeDtypeStruct((B, CFG.n_heads, HD), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((pages, PAGE, CFG.n_kv_heads, HD), jnp.int8,
                              sharding=one_chip)
    sc = jax.ShapeDtypeStruct((pages, PAGE, CFG.n_kv_heads), jnp.float32,
                              sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((B, W), jnp.int32, sharding=one_chip)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def fn(q, k, v, t, n, ks, vs):
        return mx_flash_decode(q, k, v, t, n, k_scale=ks, v_scale=vs)
    assert _compile(fn, q, kv, kv, i32, ln, sc, sc) == {"mx_flash_decode": 1}


QWEN = get_config("qwen2-0.5b")
QWEN_PAGES = 12289  # the chat cell's pool and its dump page


@pytest.mark.parametrize("W", [128, 192])
def test_qwen_decode_attention_compiles(one_chip, W):
    """qwen2-0.5b's decode attention at the chat cell's shapes: 64 slots,
    page 16, Hkv 2, 12,289 pages, at both table widths the cell reaches."""
    B = 64

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((B, QWEN.n_heads, QWEN.hd))
    kv = sds((QWEN_PAGES, PAGE, QWEN.n_kv_heads, QWEN.hd))
    census = _compile(lambda *a: mx_flash_decode(*a), q, kv, kv,
                      sds((B, W), jnp.int32), sds((B,), jnp.int32))
    assert census == {"mx_flash_decode": 1}


def test_qwen_decode_step_reads_the_pools_in_place(one_chip, pol):
    """The whole qwen2-0.5b decode step, as the chat cell runs it (f32
    pools of 12,289 pages, 64 slots, W 128): one decode attention launch,
    and no more pool-sized copies than the layer body's four (each of K
    and V sliced out of the layer stack and put back), so the kernel takes
    its pools as they lie."""
    import re

    from repro.models import build_model

    model = build_model(QWEN)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    B, W = 64, 128
    i32 = jnp.int32
    with ops.use_policy(pol):
        params = jax.tree.map(sds, model.abstract())
        cache = jax.tree.map(sds, model.make_paged_cache(
            QWEN_PAGES, PAGE, mode="abstract", dtype=jnp.float32))
        text = jax.jit(model.decode_step_paged).lower(
            params, sds(jax.ShapeDtypeStruct((B, 1), i32)), cache,
            sds(jax.ShapeDtypeStruct((B,), i32)),
            sds(jax.ShapeDtypeStruct((B, W), i32)),
            sds(jax.ShapeDtypeStruct((B,), i32))).compile().as_text()
    assert kernel_launches(text)["mx_flash_decode"] == 1
    pool = f"{QWEN_PAGES},{PAGE},{QWEN.n_kv_heads},{QWEN.hd}]"
    copies = [line for line in text.splitlines()
              if re.search(r"= \S+ copy\(", line)
              and pool in line.split(" copy(")[0]]
    assert 0 < len(copies) <= 4, copies


def test_grouped_matmul_compiles(one_chip, pol):
    """8 experts, SwiGLU up/gate at d_model -> d_ff / 4 per expert, with
    the tiles the dispatch plans for the average group."""
    E, T, F = 8, 512, CFG.d_ff // 4

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, w, g, sizes):
        return ops.grouped_matmul(x, w, sizes, w_gate=g, activation="swiglu",
                                  policy=pol)
    census = _compile(fn, sds((T, D)), sds((E, D, F)), sds((E, D, F)),
                      sds((E,), jnp.int32))
    assert census == {"mx_grouped_matmul": 1}


def test_ring_collective_matmul_compiles(topo, pol):
    """One TP projection (up, all-gather ring) over the 4-chip model axis
    of a (1, 4) mesh: the ring's chunk GEMMs are MX kernels."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.parallel.sharding import collective_policy

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    x = jax.ShapeDtypeStruct((512, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("model", None)))
    w = jax.ShapeDtypeStruct((D, CFG.d_ff), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(None, "model")))

    def fn(x, w):
        with collective_policy(mesh):
            return ops.linear(x, w, policy=pol, tp_mode="allgather")
    census = _compile(fn, x, w)
    assert census.get("mx_matmul_fused", 0) >= 4  # one GEMM per ring step
