"""The main path's Pallas kernels compile for a TPU v5e at llama3.2-1b's
published widths, at the plans the dispatch picks itself, and the decode
attention and whole donated decode and prefill programs at the chat cells'
shapes: qwen2-0.5b on one chip, deepseek-67b per chip of a (1, 4) mesh.

Nothing here runs: each test lowers and compiles for a *described* chip
(`jax.experimental.topologies`), which raises what the chip's compiler
would raise — a block shape the TPU lowering refuses, more VMEM than the
kernel's scoped limit.  Interpret-mode tests cannot see either.  The
topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the worker running this file loads
the TPU compiler.
"""
import math
import os
import re

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
    kv = sds((2, pages, PAGE, CFG.n_kv_heads * HD))
    table, lengths = sds((B, W), jnp.int32), sds((B,), jnp.int32)
    kernel = mx_flash_decode if S == 1 else mx_flash_verify
    census = _compile(lambda *a: kernel(*a), q, kv, kv, sds((), jnp.int32),
                      table, lengths)
    assert census == {kernel.__name__: 1}


def test_int8_paged_decode_compiles(one_chip):
    """int8 KV pages with their per-row f32 scale pages."""
    B, W, pages = 8, 34, 8 * 34 + 1
    q = jax.ShapeDtypeStruct((B, CFG.n_heads, HD), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, pages, PAGE, CFG.n_kv_heads * HD),
                              jnp.int8, sharding=one_chip)
    sc = jax.ShapeDtypeStruct((2, pages, PAGE, CFG.n_kv_heads), jnp.float32,
                              sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((B, W), jnp.int32, sharding=one_chip)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fn(q, k, v, lay, t, n, ks, vs):
        return mx_flash_decode(q, k, v, lay, t, n, k_scale=ks, v_scale=vs)
    assert _compile(fn, q, kv, kv, layer, i32, ln, sc, sc) == {
        "mx_flash_decode": 1}


QWEN = get_config("qwen2-0.5b")
QWEN_PAGES = 12289  # the chat cell's pool and its dump page


@pytest.mark.parametrize("W", [128, 192])
def test_qwen_decode_attention_compiles(one_chip, W):
    """qwen2-0.5b's decode attention at the chat cell's shapes: 64 slots,
    page 16, Hkv 2, 12,289 pages a layer of 24, at both table widths the
    cell reaches."""
    B = 64

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((B, QWEN.n_heads, QWEN.hd))
    kv = sds((QWEN.n_layers, QWEN_PAGES, PAGE, QWEN.n_kv_heads * QWEN.hd))
    census = _compile(lambda *a: mx_flash_decode(*a), q, kv, kv,
                      sds((), jnp.int32), sds((B, W), jnp.int32),
                      sds((B,), jnp.int32))
    assert census == {"mx_flash_decode": 1}


_MOVES = ("copy", "copy-done", "dynamic-slice", "dynamic-update-slice", "pad")


def _pool_moves(text, pool_shape):
    """The compiled program's instructions that move the pool: a copy,
    slice, update-slice or pad (by opcode, or by the name of the fusion
    that wraps one) whose result holds as many elements as the stacked
    pool or one layer of it."""
    sizes = {math.prod(pool_shape), math.prod(pool_shape[1:])}
    moves = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                     line)
        if not m or math.prod(map(int, m[2].split(","))) not in sizes:
            continue
        if m[3] in _MOVES or any(w in m[1] for w in _MOVES):
            moves.append(line.strip()[:200])
    return moves


def _serving_program(model, name, args, donate=True):
    """``name`` of the model's paged programs, compiled with the cache
    (argument 2) donated or not."""
    fn = getattr(model, name)
    return jax.jit(fn, donate_argnums=(2,) if donate else ()).lower(
        *args).compile()


def _qwen_program(one_chip, pol, name, W, donate=True):
    from repro.models import build_model

    model = build_model(QWEN)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    with ops.use_policy(pol):
        params = jax.tree.map(sds, model.abstract())
        cache = jax.tree.map(sds, model.make_paged_cache(
            QWEN_PAGES, PAGE, mode="abstract", dtype=jnp.float32))
        if name == "decode_step_paged":
            B = 64
            args = (params, i32(B, 1), cache, i32(B), i32(B, W), i32(B))
        else:  # one 128-token chunk of one slot
            args = (params, i32(1, 128), cache, i32(1), i32(1, W))
        compiled = _serving_program(model, name, args, donate)
    pool = cache["seg0"]["k_pages"]
    return compiled, pool.shape, sum(a.size * a.dtype.itemsize
                                     for a in jax.tree.leaves(cache))


def test_qwen_decode_step_reads_the_pools_in_place(one_chip, pol):
    """The whole qwen2-0.5b decode step at the chat cell's shapes (f32
    pools of 24 layers x 12,289 pages, 64 slots).  Donated, at both table
    widths the cell reaches: one decode attention launch, no copy, slice,
    update-slice or pad of the pool or of a layer of it, and the pools'
    every byte aliased from input to output — the step writes and reads
    the pools where they lie.  Not donated, as the batcher runs it: the
    layer loop still moves nothing, and the only moves are one copy of
    each pool into the step's output."""
    for W in (128, 192):
        compiled, shape, pool_bytes = _qwen_program(
            one_chip, pol, "decode_step_paged", W)
        text = compiled.as_text()
        assert kernel_launches(text)["mx_flash_decode"] == 1
        assert _pool_moves(text, shape) == []
        assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    compiled, shape, _ = _qwen_program(one_chip, pol, "decode_step_paged",
                                       128, donate=False)
    moves = _pool_moves(compiled.as_text(), shape)
    assert len(moves) == 2, moves
    assert all(re.search(r" copy\(%cache\S*_pages", m) for m in moves), moves


@pytest.mark.parametrize("W", [128, 192])
def test_qwen_prefill_chunk_reads_the_pools_in_place(one_chip, pol, W):
    """The donated 128-token prefill chunk at the chat cell's shapes: the
    same two checks as the decode step."""
    compiled, shape, pool_bytes = _qwen_program(
        one_chip, pol, "prefill_step_paged", W)
    assert _pool_moves(compiled.as_text(), shape) == []
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_deepseek_tp4_decode_step_reads_the_pools_in_place(topo, pol):
    """deepseek-67b's donated decode step as its four-chip cell runs it:
    8 layers at the published widths, TP 4 on the ring of a (1, 4) mesh,
    pools of 6,145 pages sharded on their kv-head lanes (256 of 1024 a
    chip).  Per chip: one decode attention launch, no pool move, and the
    chip's share of the pools aliased."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from repro.models import build_model
    from repro.parallel.sharding import (
        collective_policy, make_rules, tree_shardings, use_rules)

    L, B, W, pages = 8, 32, 128, 6145
    cfg = dataclasses.replace(get_config("deepseek-67b"), n_layers=L,
                              blocks=(("dense", L),))
    model = build_model(cfg)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    rules = make_rules(mesh, profile=cfg.parallelism)

    def placed(tree, axes):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, tree_shardings(rules, tree, axes))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, P()))

    with use_rules(rules), collective_policy(mesh), ops.use_policy(pol):
        params = placed(model.abstract(), model.axes())
        cache = placed(
            model.make_paged_cache(pages, PAGE, mode="abstract",
                                   dtype=jnp.float32),
            model.make_paged_cache(pages, PAGE, mode="axes"))
        compiled = _serving_program(
            model, "decode_step_paged",
            (params, i32(B, 1), cache, i32(B), i32(B, W), i32(B)))
    text = compiled.as_text()
    shard = (L, pages, PAGE, cfg.n_kv_heads * cfg.hd // 4)
    assert cache["seg0"]["k_pages"].sharding.shard_shape(
        cache["seg0"]["k_pages"].shape) == shard
    assert kernel_launches(text)["mx_flash_decode"] == 1
    assert _pool_moves(text, shard) == []
    per_chip = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(cache)) // 4
    assert compiled.memory_analysis().alias_size_in_bytes >= per_chip


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
