"""Paged split-KV flash decode: kernel-vs-oracle sweeps over layer-stacked
(L, P, ps, Hkv * d) pools read at a layer index, paged-vs-dense decode
parity through the model stack (the acceptance bar: <= 1e-5 in f32 across
ragged batch fills), and chunked-prefill parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ops
from repro.kernels.mx_flash_decode import mx_flash_decode
from repro.kernels.ref import paged_decode_ref
from repro.models import build_model
from repro.models.layers import Attention
from repro.runtime.kv_pages import PagePool


# the kernel tests read the last layer of a two-layer stack: an index map
# that dropped the layer would read layer 0's different values
LAYERS, LAYER = 2, 1


def _pages(rng, P, ps, Hkv, d, dtype=jnp.float32, layers=LAYERS):
    """A random layer-stacked pool (layers, P, ps, Hkv * d)."""
    shape = (layers, P, ps, Hkv * d)
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _scales(rng, P, ps, Hkv, lo, hi):
    return jnp.asarray(rng.uniform(lo, hi, (LAYERS, P, ps, Hkv)), jnp.float32)


def _paged_setup(rng, B, Hkv, d, ps, W, lengths, P=None):
    P = P or (B * W + 1)
    kp = _pages(rng, P, ps, Hkv, d)
    vp = _pages(rng, P, ps, Hkv, d)
    pool = PagePool(P - 1, ps)
    for s, ln in enumerate(lengths):
        if ln > 0:
            pool.reserve(s, ln)
            pool.set_length(s, ln)
    table = jnp.asarray(pool.page_table(B, W))
    return kp, vp, table, jnp.asarray(pool.lengths(B))


@pytest.mark.parametrize(
    "B,H,Hkv,d,ps,W,lengths",
    [
        (2, 4, 4, 16, 8, 2, (5, 16)),          # MHA, ragged
        (3, 8, 2, 32, 8, 4, (1, 17, 32)),      # GQA groups=4
        (4, 6, 3, 8, 4, 3, (12, 0, 3, 7)),     # free slot + odd heads
        (1, 2, 1, 64, 16, 1, (16,)),           # single page
        # qwen2-0.5b's heads and pages (groups 7; 16 pages a block): ends
        # on a block edge, one past it, mid-page, on a page edge, a slot at
        # the full width, free slots between live ones
        (6, 14, 2, 64, 16, 192, (256, 0, 257, 3072, 0, 13)),
        (4, 14, 2, 64, 16, 192, (255, 16, 0, 512)),
        # 21 pages a block: W = 192 is not a multiple of it (the last block
        # has 3 pages)
        (5, 21, 3, 16, 8, 192, (1536, 168, 0, 169, 7)),
        # W below the block: 2 and 4 pages, groups 4
        (3, 8, 2, 32, 8, 2, (0, 16, 9)),
        (3, 8, 2, 32, 8, 4, (32, 0, 17)),
        (2, 4, 1, 16, 4, 4, (0, 0)),           # every slot free
    ],
)
def test_kernel_matches_oracle(B, H, Hkv, d, ps, W, lengths):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    kp, vp, table, lns = _paged_setup(rng, B, Hkv, d, ps, W, lengths)
    out = mx_flash_decode(q, kp, vp, LAYER, table, lns, interpret=True)
    ref = paged_decode_ref(q, kp, vp, LAYER, table, lns)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # free slots produce exactly-zero rows
    for i, ln in enumerate(lengths):
        if ln == 0:
            assert np.all(np.asarray(out[i]) == 0.0)


def test_kernel_scaled_pages_match_oracle():
    """int8-cache layout: per-row dequant scale pages steered by the same
    table must match the oracle's gathered dequantization."""
    rng = np.random.default_rng(1)
    B, H, Hkv, d, ps, W = 3, 8, 4, 16, 8, 3
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    P = B * W + 1
    kp = _pages(rng, P, ps, Hkv, d, jnp.int8)
    vp = _pages(rng, P, ps, Hkv, d, jnp.int8)
    ks = _scales(rng, P, ps, Hkv, 0.001, 0.05)
    vs = _scales(rng, P, ps, Hkv, 0.001, 0.05)
    pool = PagePool(P - 1, ps)
    lengths = (20, 3, 24)
    for s, ln in enumerate(lengths):
        pool.reserve(s, ln)
        pool.set_length(s, ln)
    table = jnp.asarray(pool.page_table(B, W))
    lns = jnp.asarray(pool.lengths(B))
    out = mx_flash_decode(q, kp.astype(jnp.float32), vp.astype(jnp.float32),
                          LAYER, table, lns, k_scale=ks, v_scale=vs,
                          interpret=True)
    ref = paged_decode_ref(q, kp, vp, LAYER, table, lns, k_scale=ks,
                           v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "B,H,Hkv,d,ps,W,lengths",
    [
        (4, 14, 2, 64, 16, 24, (256, 0, 384, 7)),   # block edge, free, mid
        (3, 8, 2, 16, 8, 4, (32, 0, 9)),            # W below the block
    ],
)
def test_kernel_scaled_pages_across_blocks(B, H, Hkv, d, ps, W, lengths):
    """int8 pages with their scale pages over several blocks a slot and
    free slots between live ones."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    P = B * W + 1
    kp = _pages(rng, P, ps, Hkv, d, jnp.int8)
    vp = _pages(rng, P, ps, Hkv, d, jnp.int8)
    ks = _scales(rng, P, ps, Hkv, 0.001, 0.05)
    vs = _scales(rng, P, ps, Hkv, 0.001, 0.05)
    _, _, table, lns = _paged_setup(rng, B, Hkv, d, ps, W, lengths, P)
    out = mx_flash_decode(q, kp, vp, LAYER, table, lns, k_scale=ks,
                          v_scale=vs, interpret=True)
    ref = paged_decode_ref(q, kp, vp, LAYER, table, lns, k_scale=ks,
                           v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    for i, ln in enumerate(lengths):
        if ln == 0:
            assert np.all(np.asarray(out[i]) == 0.0)


@pytest.mark.parametrize("lengths,W", [
    ((256, 0, 257, 3072, 0, 13), 192),   # every operand live somewhere
    ((3072, 17, 0, 300, 1, 0, 2048, 5), 192),
    ((0, 0, 0), 8),                      # no live slot: one idle step
])
def test_schedule_copies_each_live_page_once(lengths, W):
    """The walk's fetch table, played through a pipeline that copies a
    page operand only when its index changes: every live page of every
    slot is copied exactly once, and no other page is."""
    from repro.kernels.mx_flash_decode import _schedule

    B, ps, ppb = len(lengths), 16, 16
    nb = -(-W // ppb)
    table = np.arange(B * W, dtype=np.int32).reshape(B, W) + 1
    steps, slot, blk, npg, fetch = (np.asarray(a) for a in _schedule(
        jnp.asarray(table), jnp.asarray(lengths, jnp.int32), ps, ppb, nb))
    want = sorted(int(table[i, j]) for i, n in enumerate(lengths)
                  for j in range(-(-n // ps)))
    assert int(steps) == max(sum(-(-n // (ps * ppb)) for n in lengths), 1)
    assert [(int(a), int(b)) for a, b in zip(slot[:steps], blk[:steps])
            if lengths[a]] == [(i, b) for i, n in enumerate(lengths)
                                for b in range(-(-n // (ps * ppb)))]
    fetch = fetch.reshape(-1, ppb)[:steps]
    copied = [int(fetch[0, p]) for p in range(ppb)]
    copied += [int(fetch[t, p]) for t in range(1, steps) for p in range(ppb)
               if fetch[t, p] != fetch[t - 1, p]]
    if want:
        assert sorted(copied) == want
    else:
        assert len(set(copied)) == 1   # the idle step's one operand page


def test_stale_page_contents_are_dead():
    """Recycled pages carry a previous tenant's K/V; the length mask must
    make them unreachable — poisoning every non-resident page with huge
    values must not change the output."""
    rng = np.random.default_rng(2)
    B, H, Hkv, d, ps, W = 2, 4, 2, 16, 4, 2
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    kp, vp, table, lns = _paged_setup(rng, B, Hkv, d, ps, W, (6, 3))
    ref = paged_decode_ref(q, kp, vp, LAYER, table, lns)
    # poison: rows at positions >= length inside resident pages AND whole
    # unallocated pages, and every other layer.  Build a mask of live
    # (layer, page, row) coordinates.
    live = np.zeros(kp.shape[:3], bool)
    tbl = np.asarray(table)
    for s, ln in enumerate((6, 3)):
        for j in range(W):
            for r in range(ps):
                if j * ps + r < ln:
                    live[LAYER, tbl[s, j], r] = True
    mask = jnp.asarray(live)[..., None]
    poison_k = jnp.where(mask, kp, 1e30)
    poison_v = jnp.where(mask, vp, 1e30)
    out = paged_decode_ref(q, poison_k, poison_v, LAYER, table, lns)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    outk = mx_flash_decode(q, poison_k, poison_v, LAYER, table, lns,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(outk), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_reads_only_its_layer():
    """Two layers hold different values in the same pages: at each layer
    the kernel matches the oracle on that layer alone, and poisoning every
    other layer changes nothing."""
    rng = np.random.default_rng(6)
    B, H, Hkv, d, ps, W = 3, 8, 2, 32, 8, 4
    lengths = (29, 0, 11)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    kp, vp, table, lns = _paged_setup(rng, B, Hkv, d, ps, W, lengths)
    outs = []
    for layer in range(LAYERS):
        out = mx_flash_decode(q, kp, vp, layer, table, lns, interpret=True)
        alone = paged_decode_ref(q, kp[layer:layer + 1], vp[layer:layer + 1],
                                 0, table, lns)
        np.testing.assert_allclose(np.asarray(out), np.asarray(alone),
                                   rtol=1e-5, atol=1e-5)
        other = (jnp.arange(LAYERS) != layer)[:, None, None, None]
        poisoned = mx_flash_decode(q, jnp.where(other, 1e30, kp),
                                   jnp.where(other, 1e30, vp), layer, table,
                                   lns, interpret=True)
        np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(out))
        outs.append(np.asarray(out))
    assert not np.allclose(outs[0], outs[1])


@pytest.mark.parametrize("H,Hkv,d", [(14, 2, 64), (64, 8, 128)],
                         ids=["hkv2x64", "hkv8x128"])
def test_kernel_f32_parity(H, Hkv, d):
    """f32 pools at the served row widths (128 and 1024 lanes): the kernel
    agrees with `paged_decode_ref` to f32 rounding, over ragged lengths
    that span several blocks, a free slot among them."""
    rng = np.random.default_rng(7)
    B, ps, W = 4, 16, 12
    lengths = (170, 0, 192, 1)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    kp, vp, table, lns = _paged_setup(rng, B, Hkv, d, ps, W, lengths)
    out = mx_flash_decode(q, kp, vp, LAYER, table, lns, interpret=True)
    ref = paged_decode_ref(q, kp, vp, LAYER, table, lns)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# layer-level: Attention.decode_paged vs Attention.decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas_mx"])
def test_attention_paged_matches_dense(backend):
    """The acceptance bar: paged decode == dense decode to <= 1e-5 (f32)
    at ragged per-slot positions, on both the oracle and kernel paths."""
    attn = Attention(d_model=32, n_heads=4, n_kv_heads=2)
    p = attn.init(jax.random.PRNGKey(0))
    B, max_len, ps = 4, 16, 4
    rng = np.random.default_rng(0)
    dense = attn.init_cache(B, max_len, dtype=jnp.float32)
    pool = PagePool(B * (max_len // ps), ps)
    for s in range(B):
        pool.reserve(s, max_len)
    paged = attn.init_paged_cache(pool.total_pages, ps, dtype=jnp.float32)
    width = max_len // ps

    # ragged fill: slot i starts decoding at depth i*2
    policy = ops.MXPolicy(backend=backend, interpret=True)
    with ops.use_policy(policy):
        for t in range(8):
            idx = np.array([min(t + 2 * i, max_len - 1) for i in range(B)],
                           np.int32)
            x = jnp.asarray(rng.standard_normal((B, 1, 32)), jnp.float32)
            for s in range(B):
                pool.set_length(s, int(idx[s]) + 1)
            table = jnp.asarray(pool.page_table(B, width))
            lns = jnp.asarray(pool.lengths(B))
            od, dense = attn.decode(p, x, dense, jnp.asarray(idx))
            op, paged = attn.decode_paged(p, x, paged, 0, jnp.asarray(idx),
                                          table, lns)
            np.testing.assert_allclose(np.asarray(od), np.asarray(op),
                                       rtol=1e-5, atol=1e-5)


def test_attention_paged_int8_roundtrip():
    """int8 paged cache: quantize-on-write / dequant-on-read keeps the
    attention output close to the f32 cache (per-row scales bound the
    error to int8 resolution)."""
    from repro.core.precision import QuantSpec
    attn = Attention(d_model=32, n_heads=4, n_kv_heads=2)
    p = attn.init(jax.random.PRNGKey(0))
    B, max_len, ps = 2, 8, 4
    rng = np.random.default_rng(3)
    pool = PagePool(B * (max_len // ps), ps)
    for s in range(B):
        pool.reserve(s, max_len)
    f32c = attn.init_paged_cache(pool.total_pages, ps, dtype=jnp.float32)
    q8c = attn.init_paged_cache(pool.total_pages, ps,
                                kv_quant=QuantSpec("int8", "tile"))
    assert q8c["k_pages"].dtype == jnp.int8 and "k_scale" in q8c
    width = max_len // ps
    for t in range(6):
        x = jnp.asarray(rng.standard_normal((B, 1, 32)), jnp.float32)
        idx = jnp.full((B,), t, jnp.int32)
        for s in range(B):
            pool.set_length(s, t + 1)
        table = jnp.asarray(pool.page_table(B, width))
        lns = jnp.asarray(pool.lengths(B))
        of, f32c = attn.decode_paged(p, x, f32c, 0, idx, table, lns)
        oq, q8c = attn.decode_paged(p, x, q8c, 0, idx, table, lns)
        err = float(jnp.abs(of - oq).max())
        scale = float(jnp.abs(of).max())
        assert err < 0.05 * max(scale, 1.0), (t, err, scale)


# ---------------------------------------------------------------------------
# model-level: decode_step_paged vs decode_step, chunked prefill
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_model_paged_decode_matches_dense_ragged():
    cfg = get_config("llama3.2-1b-smoke")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, max_len, ps = 3, 16, 4
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (B, 8), 0,
                                         cfg.vocab))
    dc = model.make_cache(B, max_len, mode="init", dtype=jnp.float32)
    pool = PagePool(B * (max_len // ps), ps)
    for s in range(B):
        pool.reserve(s, max_len)
    pc = model.make_paged_cache(pool.total_pages, ps, mode="init",
                                dtype=jnp.float32)
    width = max_len // ps
    errs = []
    for t in range(8):
        idx = jnp.full((B,), t, jnp.int32)
        ld, dc = model.decode_step(params, toks[:, t:t + 1], dc, idx)
        for s in range(B):
            pool.set_length(s, t + 1)
        table = jnp.asarray(pool.page_table(B, width))
        lns = jnp.asarray(pool.lengths(B))
        lp, pc = model.decode_step_paged(params, jnp.asarray(toks[:, t:t + 1]),
                                         pc, idx, table, lns)
        errs.append(float(jnp.abs(ld - lp).max()))
    assert max(errs) <= 1e-5, errs


def test_paged_cache_modes_agree():
    """abstract/axes paged-cache trees mirror the real tree (the dry-run
    contract make_cache already satisfies)."""
    cfg = get_config("llama3.2-1b-smoke")
    model = build_model(cfg)
    real = model.make_paged_cache(9, 4, mode="init")
    abstract = model.make_paged_cache(9, 4, mode="abstract")
    rs = jax.tree.map(lambda a: (a.shape, str(a.dtype)), real)
    ab = jax.tree.map(lambda a: (a.shape, str(a.dtype)), abstract)
    assert rs == ab
    axes = model.make_paged_cache(9, 4, mode="axes")
    n = len(jax.tree.leaves(real))
    na = len(jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)
                             and all(e is None or isinstance(e, str) for e in x)))
    assert n == na


def test_unsupported_arch_raises():
    cfg = get_config("zamba2-2.7b-smoke")
    model = build_model(cfg)
    assert not model.supports_paged()
    with pytest.raises(ValueError):
        model.make_paged_cache(8, 4)


@pytest.mark.slow
def test_chunked_prefill_matches_token_stepping():
    """prefill_step in chunks == the same tokens stepped one at a time:
    identical last logits AND identical cache (so decode continues
    seamlessly)."""
    cfg = get_config("llama3.2-1b-smoke")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, max_len = 2, 7, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    stepped = model.make_cache(B, max_len, mode="init", dtype=jnp.float32)
    for t in range(S):
        lg_s, stepped = model.decode_step(params, toks[:, t:t + 1], stepped, t)
    chunked = model.make_cache(B, max_len, mode="init", dtype=jnp.float32)
    t = 0
    for c in (3, 2, 2):  # uneven chunks
        lg_c, chunked = model.prefill_step(params, toks[:, t:t + c], chunked, t)
        t += c
    np.testing.assert_allclose(np.asarray(lg_c[:, -1]), np.asarray(lg_s[:, -1]),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(stepped), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    # decode after the chunked prefill continues identically
    nt = jnp.argmax(lg_c[:, -1], axis=-1).astype(jnp.int32)[:, None]
    ld, _ = model.decode_step(params, nt, stepped, S)
    lc, _ = model.decode_step(params, nt, chunked, S)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lc),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# batched-verify kernel (the S-row speculative window)
# ---------------------------------------------------------------------------


def _verify_setup(rng, B, Hkv, d, ps, W, lengths, S):
    """Pages are random EVERYWHERE — rows past each slot's live length are
    stale garbage the masks must keep dead."""
    return _paged_setup(rng, B, Hkv, d, ps, W, lengths)


def _verify_oracle(q, kp, vp, table, lns, *, k_scale=None, v_scale=None):
    """paged_prefill_ref at index = lengths - S, with free-slot rows
    zeroed (the ref's empty-mask softmax is NaN there by construction)."""
    from repro.kernels.ref import paged_prefill_ref

    S = q.shape[1]
    ref = paged_prefill_ref(q, kp, vp, LAYER, table, lns - S,
                            k_scale=k_scale, v_scale=v_scale)
    return jnp.where((lns > 0)[:, None, None, None], ref, 0.0)


@pytest.mark.parametrize(
    "B,H,Hkv,d,ps,W,S,lengths",
    [
        (2, 4, 4, 16, 8, 3, 4, (9, 17)),       # MHA, ragged, window spans pages
        (3, 8, 2, 32, 8, 4, 5, (5, 13, 32)),   # GQA groups=4, S > min length? no: 5<=5
        (4, 6, 3, 8, 4, 4, 5, (12, 0, 5, 16)), # free slot + S > page_size
        (1, 2, 1, 16, 16, 2, 2, (18,)),        # window crosses page boundary
        # qwen2-0.5b's heads and pages: a one-row and a three-row window,
        # across a block edge and at the full width, free slots between
        (4, 14, 2, 64, 16, 24, 1, (257, 0, 384, 5)),
        (4, 14, 2, 64, 16, 24, 3, (0, 258, 384, 3)),
    ],
)
def test_verify_kernel_matches_prefill_oracle(B, H, Hkv, d, ps, W, S, lengths):
    from repro.kernels.mx_flash_decode import mx_flash_verify

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
    kp, vp, table, lns = _verify_setup(rng, B, Hkv, d, ps, W, lengths, S)
    out = mx_flash_verify(q, kp, vp, LAYER, table, lns, interpret=True)
    ref = _verify_oracle(q, kp, vp, table, lns)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    for i, ln in enumerate(lengths):
        if ln == 0:
            assert np.all(np.asarray(out[i]) == 0.0)


def test_verify_s1_matches_decode_kernel():
    """The degenerate 1-row window IS a decode step: both kernels run the
    same online softmax over the same steered pages."""
    from repro.kernels.mx_flash_decode import mx_flash_verify

    rng = np.random.default_rng(2)
    B, H, Hkv, d, ps, W = 3, 8, 4, 16, 8, 3
    lengths = (7, 0, 20)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    kp, vp, table, lns = _verify_setup(rng, B, Hkv, d, ps, W, lengths, 1)
    ver = mx_flash_verify(q[:, None], kp, vp, LAYER, table, lns,
                          interpret=True)
    dec = mx_flash_decode(q, kp, vp, LAYER, table, lns, interpret=True)
    np.testing.assert_allclose(np.asarray(ver[:, 0]), np.asarray(dec),
                               rtol=1e-6, atol=1e-6)


def test_verify_scaled_pages_match_oracle():
    """int8-cache layout: the window kernel steers the same per-row scale
    pages as decode and must match the dequantizing oracle."""
    from repro.kernels.mx_flash_decode import mx_flash_verify

    rng = np.random.default_rng(3)
    B, H, Hkv, d, ps, W, S = 2, 4, 2, 16, 8, 3, 3
    lengths = (11, 24)
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
    kp, vp, table, lns = _verify_setup(rng, B, Hkv, d, ps, W, lengths, S)
    P = kp.shape[1]
    ks = _scales(rng, P, ps, Hkv, 0.5, 2.0)
    vs = _scales(rng, P, ps, Hkv, 0.5, 2.0)
    out = mx_flash_verify(q, kp, vp, LAYER, table, lns, k_scale=ks,
                          v_scale=vs, interpret=True)
    ref = _verify_oracle(q, kp, vp, table, lns, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_verify_causal_within_window():
    """Row r must NOT see rows r+1..S-1 of its own window: perturbing a
    later window position's K/V leaves earlier rows' outputs unchanged."""
    from repro.kernels.mx_flash_decode import mx_flash_verify

    rng = np.random.default_rng(4)
    B, H, Hkv, d, ps, W, S = 1, 2, 2, 8, 4, 3, 3
    lengths = (9,)
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
    kp, vp, table, lns = _verify_setup(rng, B, Hkv, d, ps, W, lengths, S)
    base = np.asarray(mx_flash_verify(q, kp, vp, LAYER, table, lns,
                                      interpret=True))
    # position of the LAST window row is lengths-1 = 8 -> page 2, row 0
    tbl = np.asarray(table)
    pg, row = tbl[0, 8 // ps], 8 % ps
    kp2 = kp.at[LAYER, pg, row].set(99.0)
    vp2 = vp.at[LAYER, pg, row].set(-99.0)
    pert = np.asarray(mx_flash_verify(q, kp2, vp2, LAYER, table, lns,
                                      interpret=True))
    np.testing.assert_array_equal(pert[:, :2], base[:, :2])
    assert not np.allclose(pert[:, 2], base[:, 2])
