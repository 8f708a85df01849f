"""Quantized MX path: PrecisionPolicy plumbing + numerics parity.

Tolerance tiers (documented in README "Quantized MX path"):

  TIER_EXACT — the Pallas kernel vs the dequantized UNFUSED reference over
      the same narrow payloads.  Both run dot_f32 on identical quantized
      values; the only divergence is f32 summation order across k blocks,
      so the bound is float-rounding-sized.
  TIER_QUANT — quantized vs the true f32 GEMM.  Bounded by the
      quantization error itself: symmetric int8 round-to-nearest gives a
      per-element operand error <= scale/2, which accumulates over K as
      ~sqrt(K)/127 relative RMS.  We assert max-abs error <= 5% of the
      reference amax (orders looser than observed, orders tighter than a
      wrong-scale bug, which shows up as O(100%)).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ops
from repro.core.precision import (
    NAMED_POLICIES,
    PrecisionPolicy,
    QuantSpec,
    calibrate_static_scale,
    current_precision,
    resolve_precision,
    use_precision,
)
from repro.core.transfer_model import GemmProblem, PallasGemmTiling
from repro.kernels.mx_grouped_matmul import grouped_matmul_reference, mx_grouped_matmul
from repro.kernels.mx_matmul import Epilogue, apply_epilogue, dot_f32, mx_matmul_fused
from repro.kernels.quant import (
    dequantize,
    executed_gemm_bytes,
    quantize,
    quantize_int8_stochastic,
    quantize_int8_tensor,
    quantize_operand,
)

TIER_EXACT = 2e-5   # kernel vs dequantized-unfused reference (same payloads)
TIER_QUANT = 0.05   # quantized vs true f32, fraction of the reference amax

POL_MX = ops.MXPolicy(backend="pallas_mx", bm=32, bn=32, bk=32, interpret=True)
POL_XLA = ops.MXPolicy(backend="xla")
INT8_TILE = PrecisionPolicy(a=QuantSpec("int8", "tile"), b=QuantSpec("int8", "tile"))


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape) * scale, jnp.float32
    )


# ---------------------------------------------------------------------------
# QuantSpec / PrecisionPolicy metadata
# ---------------------------------------------------------------------------


def test_spec_and_policy_validation():
    with pytest.raises(ValueError):
        QuantSpec("int4")
    with pytest.raises(ValueError):
        QuantSpec("int8", "block")
    with pytest.raises(ValueError):
        PrecisionPolicy(acc="bf16")
    with pytest.raises(ValueError):
        resolve_precision("int7")
    assert resolve_precision(None) is None
    assert resolve_precision("none") is None  # "no declaration": ambient applies
    # "f32" is a FORCING identity policy: overrides an ambient context
    f32 = resolve_precision("f32")
    assert isinstance(f32, PrecisionPolicy) and f32.is_noop_for(
        jnp.float32, jnp.float32)
    p = resolve_precision("int8")
    assert p.b.dtype == "int8" and p.a.dtype == "bf16"  # weights-int8 default
    assert resolve_precision(p) is p


def test_policy_per_operand_bytes_and_noop():
    p = NAMED_POLICIES["int8"]
    assert p.a_bytes(4) == 2 and p.b_bytes(4) == 1 and p.out_bytes(4) == 4
    assert not p.is_noop_for(jnp.float32, jnp.float32)
    f32ish = PrecisionPolicy()
    assert f32ish.is_noop_for(jnp.float32, jnp.float32)
    # bf16 spec on an already-bf16 operand is the identity
    bf = PrecisionPolicy(a=QuantSpec("bf16"), b=QuantSpec("bf16"))
    assert bf.is_noop_for(jnp.bfloat16, jnp.bfloat16)
    assert not bf.is_noop_for(jnp.float32, jnp.bfloat16)


def test_use_precision_context_and_override():
    assert current_precision() is None
    with use_precision("int8_all") as p:
        assert current_precision() is p is NAMED_POLICIES["int8_all"]
        with use_precision(None):
            assert current_precision() is None
        assert current_precision() is p
    assert current_precision() is None


# ---------------------------------------------------------------------------
# quantize/dequantize round trip (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(log_scale=st.floats(-3, 3), seed=st.integers(0, 1000),
       granularity=st.sampled_from(["tensor", "tile"]))
def test_int8_roundtrip_error_bounded(log_scale, seed, granularity):
    """Reconstruction error of symmetric int8 is <= scale/2 per element."""
    x = _rand((24, 40), seed, 10.0 ** log_scale)
    q, s = quantize_operand(x, QuantSpec("int8", granularity), "a")
    assert q.dtype == jnp.int8 and s.shape == (24, 1)
    err = np.abs(np.asarray(dequantize(q, s) - x))
    bound = np.asarray(s) * 0.5 + 1e-7
    assert (err <= bound).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_fp8_roundtrip_relative_error(seed):
    """e4m3 has a 3-bit mantissa: relative reconstruction error <= 2^-3
    (away from the clipped top bin)."""
    x = _rand((16, 32), seed, 5.0)
    q, s = quantize_operand(x, QuantSpec("fp8_e4m3", "tile"), "b")
    assert s.shape == (1, 32)
    rel = np.abs(np.asarray(dequantize(q, s) - x)) / (np.abs(np.asarray(x)) + 1e-9)
    assert rel.max() <= 2.0 ** -3 + 1e-6


def test_quantize_operand_shapes_and_zero():
    a, sa = quantize_operand(jnp.zeros((8, 16)), QuantSpec("int8", "tile"), "a")
    assert float(jnp.abs(dequantize(a, sa)).max()) == 0.0
    w3 = _rand((4, 16, 12), 0)
    qb, sb = quantize_operand(w3, QuantSpec("int8", "tile"), "b")
    assert sb.shape == (4, 1, 12)  # per expert, per output column
    qt, st_ = quantize_operand(w3, QuantSpec("int8", "tensor"), "b")
    assert st_.shape == (4, 1, 12)  # broadcast to the uniform tile layout
    assert len(set(np.asarray(st_).ravel().tolist())) == 1
    cast, none = quantize_operand(w3, QuantSpec("bf16"), "b")
    assert cast.dtype == jnp.bfloat16 and none is None


def test_compression_wire_format_is_the_shared_quantizer():
    """optim.compression's quantize IS kernels.quant.quantize_int8_tensor
    (satellite: one int8 implementation, same wire format)."""
    from repro.optim import compression

    assert compression.quantize is quantize_int8_tensor
    x = _rand((64,), 3, 100.0)
    q, s = compression.quantize(x)
    assert q.dtype == jnp.int8 and s.shape == () and s.dtype == jnp.float32
    err = np.abs(np.asarray(compression.dequantize(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6


# ---------------------------------------------------------------------------
# plain kernel parity (int8, per-tile and per-tensor scales)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("granularity", ["tile", "tensor"])
def test_mx_matmul_int8_matches_references(granularity):
    a = _rand((96, 160), 0) * jnp.asarray(
        10.0 ** np.random.default_rng(9).integers(-2, 3, size=(96, 1)))
    b = _rand((160, 80), 1, 0.1)
    spec = QuantSpec("int8", granularity)
    qa, a_s = quantize_operand(a, spec, "a")
    qb, b_s = quantize_operand(b, spec, "b")
    ep = Epilogue(a_scale=True, b_scale=True)
    got = mx_matmul_fused(qa, qb, epilogue=ep, a_scale=a_s, b_scale=b_s,
                          bm=32, bn=32, bk=64, out_dtype=jnp.float32,
                          interpret=True)
    emul = apply_epilogue(dot_f32(qa, qb), ep, a_scale=a_s, b_scale=b_s,
                          out_dtype=jnp.float32)
    ref = jnp.dot(a, b, preferred_element_type=jnp.float32)
    assert float(jnp.abs(got - emul).max()) <= TIER_EXACT * float(jnp.abs(emul).max() + 1)
    assert float(jnp.abs(got - ref).max()) <= TIER_QUANT * float(jnp.abs(ref).max())


def test_per_tile_scales_beat_per_tensor_on_skewed_rows():
    """Row-skewed activations are the case per-tile granularity exists for:
    one tensor-wide amax crushes the small rows' resolution."""
    rows = jnp.asarray(10.0 ** np.arange(-3, 5), jnp.float32)[:, None]
    a = _rand((8, 64), 0) * rows
    b = _rand((64, 32), 1, 0.1)
    ref = jnp.dot(a, b, preferred_element_type=jnp.float32)

    def err(granularity):
        spec = QuantSpec("int8", granularity)
        qa, a_s = quantize_operand(a, spec, "a")
        qb, b_s = quantize_operand(b, spec, "b")
        y = apply_epilogue(dot_f32(qa, qb), Epilogue(a_scale=True, b_scale=True),
                           a_scale=a_s, b_scale=b_s, out_dtype=jnp.float32)
        # normalize per row so the tiny rows count
        return float(jnp.abs((y - ref) / (jnp.abs(ref).max(axis=1, keepdims=True)
                                          + 1e-9)).max())

    assert err("tile") < err("tensor") / 10


def test_epilogue_scale_validation():
    with pytest.raises(ValueError):
        apply_epilogue(jnp.ones((4, 4)), Epilogue(a_scale=True))
    with pytest.raises(ValueError):
        apply_epilogue(jnp.ones((4, 4)), Epilogue(),
                       a_scale=jnp.ones((4, 1)))
    with pytest.raises(ValueError):  # bg_scale without gated+b_scale
        apply_epilogue(jnp.ones((4, 4)), Epilogue(b_scale=True),
                       b_scale=jnp.ones((1, 4)), bg_scale=jnp.ones((1, 4)))
    with pytest.raises(ValueError):
        mx_matmul_fused(jnp.ones((8, 8), jnp.int8), jnp.ones((8, 8), jnp.int8),
                        epilogue=Epilogue(a_scale=True), interpret=True)
    # scales count as fused elementwise ops for the traffic credit
    assert Epilogue(a_scale=True, b_scale=True).n_fused_ops == 2


# ---------------------------------------------------------------------------
# ops dispatch: backends agree on the SAME quantized values
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["int8", "int8_all", "int8_tensor", "bf16",
                                  "fp8", "fp8_all"])
def test_linear_backend_parity_full_epilogue(name):
    x = _rand((2, 24, 64), 0)
    w = _rand((64, 48), 1, 0.1)
    bias = _rand((48,), 2)
    res = _rand((2, 24, 48), 3)
    kw = dict(activation="gelu", residual=res, out_dtype=jnp.float32,
              precision=name)
    got = ops.linear(x, w, bias, policy=POL_MX, **kw)
    ref = ops.linear(x, w, bias, policy=POL_XLA, **kw)
    f32 = ops.linear(x, w, bias, policy=POL_XLA, activation="gelu",
                     residual=res, out_dtype=jnp.float32)
    assert float(jnp.abs(got - ref).max()) <= TIER_EXACT * float(jnp.abs(f32).max() + 1)
    assert float(jnp.abs(got - f32).max()) <= TIER_QUANT * float(jnp.abs(f32).max() + 1)


def test_linear_swiglu_quantized_gate_has_own_scales():
    x = _rand((32, 64), 0)
    w = _rand((64, 48), 1, 0.1)
    wg = _rand((64, 48), 2, 0.1)
    got = ops.linear(x, w, w_gate=wg, activation="swiglu", policy=POL_MX,
                     out_dtype=jnp.float32, precision="int8_all")
    ref = ops.linear(x, w, w_gate=wg, activation="swiglu", policy=POL_XLA,
                     out_dtype=jnp.float32, precision="int8_all")
    f32 = ops.linear(x, w, w_gate=wg, activation="swiglu", policy=POL_XLA,
                     out_dtype=jnp.float32)
    assert float(jnp.abs(got - ref).max()) <= TIER_EXACT * float(jnp.abs(f32).max() + 1)
    assert float(jnp.abs(got - f32).max()) <= TIER_QUANT * float(jnp.abs(f32).max() + 1)


def test_ambient_context_routes_linear_and_explicit_wins():
    x, w = _rand((16, 32), 0), _rand((32, 24), 1, 0.1)
    plain = ops.linear(x, w, policy=POL_MX, out_dtype=jnp.float32)
    with use_precision("int8_all"):
        ctx = ops.linear(x, w, policy=POL_MX, out_dtype=jnp.float32)
        inherit = ops.linear(x, w, policy=POL_MX, out_dtype=jnp.float32,
                             precision="none")
        forced = ops.linear(x, w, policy=POL_MX, out_dtype=jnp.float32,
                            precision="f32")
    expl2 = ops.linear(x, w, policy=POL_MX, out_dtype=jnp.float32,
                       precision="int8_all")
    assert not bool(jnp.all(ctx == plain))   # context quantized
    assert bool(jnp.all(ctx == expl2))       # same policy, same payloads
    assert bool(jnp.all(inherit == ctx))     # "none" = no declaration: inherit
    assert bool(jnp.all(forced == plain))    # "f32" forces full precision


def test_matmul_precision_and_out_override():
    x, w = _rand((16, 32), 0), _rand((32, 24), 1, 0.1)
    q = ops.matmul(x, w, policy=POL_MX, out_dtype=jnp.float32,
                   precision="int8_all")
    ref = jnp.dot(x, w, preferred_element_type=jnp.float32)
    assert float(jnp.abs(q - ref).max()) <= TIER_QUANT * float(jnp.abs(ref).max())
    p = PrecisionPolicy(b=QuantSpec("int8", "tile"), out="bf16")
    y = ops.linear(x, w, policy=POL_MX, precision=p)
    assert y.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# grouped (MoE) variant: per-expert scales via the group-offset prefetch
# ---------------------------------------------------------------------------


def test_grouped_int8_parity_ragged_and_empty_groups():
    G, K, N, T = 4, 64, 48, 96
    sizes = jnp.asarray([20, 0, 37, 15], jnp.int32)  # ragged + empty + tail
    x = _rand((T, K), 0)
    w = _rand((G, K, N), 1, 0.1)
    qa, a_s = quantize_operand(x, QuantSpec("int8", "tile"), "a")
    qb, b_s = quantize_operand(w, QuantSpec("int8", "tile"), "b")
    got = mx_grouped_matmul(qa, qb, sizes, a_scale=a_s, b_scale=b_s,
                            bm=16, bn=16, bk=32, out_dtype=jnp.float32,
                            interpret=True)
    emul = grouped_matmul_reference(dequantize(qa, a_s), dequantize(qb, b_s),
                                    sizes, out_dtype=jnp.float32)
    ref = grouped_matmul_reference(x, w, sizes, out_dtype=jnp.float32)
    assert float(jnp.abs(got - emul).max()) <= TIER_EXACT * float(jnp.abs(ref).max() + 1)
    assert float(jnp.abs(got - ref).max()) <= TIER_QUANT * float(jnp.abs(ref).max() + 1)
    # rows past sum(sizes) stay zero through the quantized path too
    assert float(jnp.abs(got[int(sizes.sum()):]).max()) == 0.0


@pytest.mark.parametrize("activation", ["none", "swiglu"])
def test_ops_grouped_matmul_backend_parity(activation):
    G, C, D, F = 4, 16, 32, 24
    x = _rand((G * C, D), 0)
    w = _rand((G, D, F), 1, 0.1)
    wg = _rand((G, D, F), 2, 0.1) if activation == "swiglu" else None
    sizes = jnp.full((G,), C, jnp.int32)
    kw = dict(activation=activation, w_gate=wg, out_dtype=jnp.float32,
              precision="int8_all")
    got = ops.grouped_matmul(x, w, sizes, policy=POL_MX, **kw)
    ref = ops.grouped_matmul(x, w, sizes, policy=POL_XLA, **kw)
    f32 = ops.grouped_matmul(x, w, sizes, policy=POL_XLA,
                             activation=activation, w_gate=wg,
                             out_dtype=jnp.float32)
    assert float(jnp.abs(got - ref).max()) <= TIER_EXACT * float(jnp.abs(f32).max() + 1)
    assert float(jnp.abs(got - f32).max()) <= TIER_QUANT * float(jnp.abs(f32).max() + 1)


# ---------------------------------------------------------------------------
# transfer model / plan: per-operand bytes
# ---------------------------------------------------------------------------


def test_gemm_problem_per_operand_bytes_default_to_elem_bytes():
    p = GemmProblem(64, 64, 64, 4)
    assert p.a_elem_bytes == p.b_elem_bytes == p.out_elem_bytes == 4
    q = GemmProblem(64, 64, 64, 2, b_bytes=1, out_bytes=4)
    assert (q.a_elem_bytes, q.b_elem_bytes, q.out_elem_bytes) == (2, 1, 4)


def test_hbm_bytes_per_operand_accounting():
    t = PallasGemmTiling(32, 32, 32)
    M = N = K = 128
    tr = t.hbm_transfers(GemmProblem(M, N, K, 4))
    q = GemmProblem(M, N, K, 2, b_bytes=1, out_bytes=4)
    assert t.hbm_bytes(q) == tr.a_down * 2 + tr.b_down * 1 + tr.d_up * 4
    # uniform problems are unchanged (Table IV validation relies on this)
    assert t.hbm_bytes(GemmProblem(M, N, K, 4)) == tr.total * 4


def test_epilogue_saved_bytes_uses_output_operand_bytes():
    """Satellite fix: the epilogue round-trips happen on the OUTPUT."""
    t = PallasGemmTiling(32, 32, 32, fused_epilogue_ops=3)
    M, N = 64, 96
    p_int8_in_f32_out = GemmProblem(M, N, 128, 1, b_bytes=1, out_bytes=4)
    assert t.epilogue_saved_bytes(p_int8_in_f32_out) == 3 * 2 * M * N * 4
    p_bf16_out = GemmProblem(M, N, 128, 4, out_bytes=2)
    assert t.epilogue_saved_bytes(p_bf16_out) == 3 * 2 * M * N * 2
    # explicit override still wins
    assert t.epilogue_saved_bytes(p_bf16_out, out_bytes=8) == 3 * 2 * M * N * 8


def test_plan_quantized_key_and_traffic_ratio():
    pol = ops.MXPolicy(backend="pallas_mx", bm=128, bn=128, bk=128)
    ops.plan_cache_clear()
    f32 = pol.plan(1024, 1024, 1024, 4)
    q = pol.plan(1024, 1024, 1024, 1, b_bytes=1, out_bytes=4)
    assert ops.plan_cache_info().currsize == 2  # distinct LRU keys
    # int8 operands with f32 out: >= 2x less traffic at 1024^3
    assert q.hbm_bytes <= 0.5 * f32.hbm_bytes
    # int8 shrinks the input working set in VMEM too
    assert q.vmem_bytes < f32.vmem_bytes


def test_model_agrees_with_executed_bytes_within_10pct():
    """The acceptance check at test scale: policy traffic model vs the
    as-executed byte count of the concrete launch (payloads + scales)."""
    M = N = K = 512
    a = _rand((M, K), 0)
    b = _rand((K, N), 1, 0.1)
    qa, a_s = quantize_operand(a, QuantSpec("int8", "tile"), "a")
    qb, b_s = quantize_operand(b, QuantSpec("int8", "tile"), "b")
    pol = ops.MXPolicy(backend="pallas_mx", bm=128, bn=128, bk=128)
    plan = pol.plan(M, N, K, 1, b_bytes=1, out_bytes=4)
    measured = executed_gemm_bytes(qa, qb, bm=128, bn=128, bk=128,
                                   out_itemsize=4, scales=(a_s, b_s))
    assert abs(plan.hbm_bytes / measured - 1.0) < 0.10


# ---------------------------------------------------------------------------
# model level: per-projection declaration
# ---------------------------------------------------------------------------


def test_transformer_block_precision_declaration():
    from repro.models.transformer import TransformerBlock

    blk_f32 = TransformerBlock(d_model=64, n_heads=4, n_kv_heads=4, d_ff=128)
    blk_q = TransformerBlock(d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                             precision="int8")
    params = blk_f32.init(jax.random.PRNGKey(0))
    x = _rand((2, 8, 64), 1)
    y_f32, _ = blk_f32(params, x)
    y_q, _ = blk_q(params, x)
    assert y_q.shape == y_f32.shape
    diff = float(jnp.abs(y_q.astype(jnp.float32) - y_f32.astype(jnp.float32)).max())
    assert 0.0 < diff <= TIER_QUANT * float(jnp.abs(y_f32).max() + 1) * 4


def test_moe_layer_precision_declaration():
    from repro.models.moe import MoE

    moe_f32 = MoE(d_model=32, d_ff=64, n_experts=4, top_k=2, n_groups=1)
    moe_q = MoE(d_model=32, d_ff=64, n_experts=4, top_k=2, n_groups=1,
                precision="int8")
    params = moe_f32.init(jax.random.PRNGKey(0))
    x = _rand((2, 16, 32), 1)
    y_f32, aux_f32 = moe_f32(params, x)
    y_q, aux_q = moe_q(params, x)
    # routing is full precision: identical aux loss, quantized expert FFNs
    assert float(jnp.abs(aux_q - aux_f32)) <= 1e-6
    diff = float(jnp.abs(y_q - y_f32).max())
    assert 0.0 < diff <= TIER_QUANT * float(jnp.abs(y_f32).max() + 1) * 4


# ---------------------------------------------------------------------------
# ring collective variant (8-device subprocess, like test_collective_matmul)
# ---------------------------------------------------------------------------

_RING_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import ops
from repro.kernels.mx_collective_matmul import (
    ChunkCompute, ring_allgather_matmul, ring_matmul_reduce_scatter,
    serialized_allgather_matmul, serialized_matmul_psum)
from repro.kernels.mx_matmul import Epilogue
from repro.kernels.quant import quantize_operand, dequantize
from repro.core.precision import QuantSpec
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import collective_policy

mesh = make_mesh((1, 8), ("data", "model"))
PZ = 8
M, K, N = 64, 32, 48
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
w = jnp.asarray(rng.normal(size=(K, N)) * 0.1, jnp.float32)
bias = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
res = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
spec = QuantSpec("int8", "tile")
qa, a_s = quantize_operand(x, spec, "a")
qb, b_s = quantize_operand(w, spec, "b")
deq = dequantize
ref_ag = jax.nn.gelu(deq(qa, a_s) @ deq(qb, b_s) + bias) + res
ref_rs = (deq(qa, a_s) @ deq(qb, b_s) + bias) + res

def sm(fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))

TOL = 2e-5 * float(jnp.abs(ref_ag).max() + 1)
ep = Epilogue(activation="gelu", bias=True, residual=True)
specs_ag = (P("model", None), P(None, "model"), P("model"), P(None, "model"),
            P("model", None), P(None, "model"))
for cc in (ChunkCompute(backend="xla"),
           ChunkCompute(backend="pallas_mx", bm=8, bn=16, bk=8, interpret=True)):
    for d in ("fwd", "bwd", "bidir"):
        got = sm(lambda xs, ws, bs, rs, asx, bsx, d=d, cc=cc: ring_allgather_matmul(
            xs, ws, axis_name="model", axis_size=PZ, compute=cc, epilogue=ep,
            bias=bs, residual=rs, a_scale=asx, b_scale=bsx,
            out_dtype=jnp.float32, direction=d),
            specs_ag, P(None, "model"))(qa, qb, bias, res, a_s, b_s)
        assert jnp.abs(got - ref_ag).max() <= TOL, (cc.backend, d)
print("AG_QUANT_OK")

ep2 = Epilogue(bias=True, residual=True)
specs_rs = (P(None, "model"), P("model", None), P(None), P("model", None),
            P(None, None), P(None, None))
for d in ("fwd", "bwd", "bidir"):
    got = sm(lambda xs, ws, bs, rs, asx, bsx, d=d: ring_matmul_reduce_scatter(
        xs, ws, axis_name="model", axis_size=PZ, compute=ChunkCompute(backend="xla"),
        epilogue=ep2, bias=bs, residual=rs, a_scale=asx, b_scale=bsx,
        out_dtype=jnp.float32, direction=d),
        specs_rs, P("model", None))(qa, qb, bias, res, a_s, b_s)
    assert jnp.abs(got - ref_rs).max() <= TOL, d
ser = sm(lambda xs, ws, bs, rs, asx, bsx: serialized_matmul_psum(
    xs, ws, axis_name="model", axis_size=PZ, compute=ChunkCompute(backend="xla"),
    epilogue=ep2, bias=bs, residual=rs, a_scale=asx, b_scale=bsx,
    out_dtype=jnp.float32), specs_rs, P("model", None))(qa, qb, bias, res, a_s, b_s)
assert jnp.abs(ser - ref_rs).max() <= TOL
ser_ag = sm(lambda xs, ws, bs, rs, asx, bsx: serialized_allgather_matmul(
    xs, ws, axis_name="model", compute=ChunkCompute(backend="xla"), epilogue=ep,
    bias=bs, residual=rs, a_scale=asx, b_scale=bsx, out_dtype=jnp.float32),
    specs_ag, P(None, "model"))(qa, qb, bias, res, a_s, b_s)
assert jnp.abs(ser_ag - ref_ag).max() <= TOL
print("RS_QUANT_OK")

# dispatch: ops.linear precision + tp_mode under a collective policy —
# overlapped ring output == the dequantized oracle (same global payloads)
with collective_policy(mesh, axis="model"):
    got = ops.linear(x, w, bias, activation="gelu", residual=res,
                     tp_mode="allgather", out_dtype=jnp.float32,
                     precision="int8_all")
    assert jnp.abs(got - ref_ag).max() <= TOL
    got = ops.linear(x, w, bias, residual=res, tp_mode="reduce_scatter",
                     out_dtype=jnp.float32, precision="int8_all")
    assert jnp.abs(got - ref_rs).max() <= TOL
    # a whole quantized transformer block under the collective policy runs
    from repro.models.transformer import TransformerBlock
    blk = TransformerBlock(d_model=64, n_heads=8, n_kv_heads=8, d_ff=128,
                           precision="int8")
    params = blk.init(jax.random.PRNGKey(0))
    xb = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    y_coll, _ = blk(params, xb)
y_plain, _ = blk(params, xb)
assert jnp.abs(y_coll - y_plain).max() <= 3e-4, float(jnp.abs(y_coll - y_plain).max())
print("DISPATCH_QUANT_OK")
print("ALL_RING_QUANT_OK")
"""


@pytest.mark.slow  # subprocess + 8-device mesh
def test_ring_collective_int8_on_8device_mesh():
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    env = {**os.environ,
           "PYTHONPATH": f"{root / 'src'}:{os.environ.get('PYTHONPATH', '')}"}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _RING_CODE], text=True,
                       capture_output=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "ALL_RING_QUANT_OK" in r.stdout


# ---------------------------------------------------------------------------
# static-scale calibration (serving decode skips the per-call amax reduce)
# ---------------------------------------------------------------------------


def _reduce_max_count(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return sum(str(e.primitive.name) == "reduce_max"
               for e in jaxpr.jaxpr.eqns)


def test_static_scale_validation():
    with pytest.raises(ValueError):
        QuantSpec("f32", static_scale=0.5)  # cast-only dtypes take no scale
    with pytest.raises(ValueError):
        QuantSpec("int8", static_scale=0.0)
    with pytest.raises(ValueError):
        calibrate_static_scale(QuantSpec("bf16"), [jnp.ones((2,))])
    with pytest.raises(ValueError):
        calibrate_static_scale(QuantSpec("int8"), [jnp.ones((2,))], margin=0)


def test_calibrate_static_scale_deletes_the_reduce():
    """The whole point of calibration: the traced quantize carries NO amax
    reduction, and the fixed scale is materialized in the same keepdims
    layout the dynamic path produces."""
    x = _rand((24, 40), 3, 2.0)
    dyn = QuantSpec("int8", "tile")
    static = calibrate_static_scale(dyn, [x, x * 0.5])
    assert static.static_scale == pytest.approx(
        float(jnp.max(jnp.abs(x))) / 127.0)
    assert _reduce_max_count(lambda v: quantize(v, dyn, axis=1), x) >= 1
    assert _reduce_max_count(lambda v: quantize(v, static, axis=1), x) == 0
    q, s = quantize(x, static, axis=1)
    qd, sd = quantize(x, dyn, axis=1)
    assert s.shape == sd.shape == (24, 1)
    assert np.allclose(np.asarray(s), static.static_scale)
    # per-tensor layout contract too
    q0, s0 = quantize(x, static, axis=None)
    assert s0.shape == ()
    # calibrated on this very tensor: reconstruction matches dynamic
    # per-tensor quality (the tile path is finer, so only coarse parity)
    err = float(jnp.abs(dequantize(q, s) - x).max())
    assert err <= static.static_scale * 0.5 + 1e-6


def test_static_scale_saturates_beyond_calibrated_range():
    """Post-training-calibration semantics: activations beyond the
    calibrated amax clip at +-qmax instead of stretching the scale."""
    calib = jnp.ones((4, 8)) * 2.0
    spec = calibrate_static_scale(QuantSpec("int8", "tensor"), [calib])
    hot = jnp.full((4, 8), 10.0)  # 5x the calibrated range
    q, s = quantize(hot, spec, axis=None)
    assert int(jnp.max(q)) == 127
    assert float(jnp.max(dequantize(q, s))) == pytest.approx(2.0, rel=0.01)
    # margin leaves headroom
    wide = calibrate_static_scale(QuantSpec("int8", "tensor"), [calib],
                                  margin=1.5)
    assert wide.static_scale == pytest.approx(2.0 * 1.5 / 127.0)


def test_static_scale_rides_quantize_operand():
    x = _rand((16, 32), 5)
    spec = calibrate_static_scale(QuantSpec("int8", "tile"), [x])
    q, s = quantize_operand(x, spec, "a")
    assert s.shape == (16, 1) and np.allclose(np.asarray(s),
                                              spec.static_scale)
    q, s = quantize_operand(x, spec, "b")
    assert s.shape == (1, 32)


# ---------------------------------------------------------------------------
# stochastic rounding (hypothesis round-trip bias)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(frac=st.floats(0.2, 0.45), seed=st.integers(0, 1000))
def test_stochastic_rounding_is_unbiased_where_nearest_is_not(frac, seed):
    """Constant-fractional-part tensors are round-to-nearest's worst case:
    every element rounds the SAME direction, a systematic bias of `frac`
    scale units.  Stochastic rounding's per-element errors are zero-mean,
    so the mean reconstruction error collapses with sqrt(N)."""
    n = 4096
    # pin the scale with one sentinel at amax=127 -> scale exactly 1.0,
    # everything else sits at integer + frac
    x = np.full((n,), 40.0 + frac, np.float32)
    x[0] = 127.0
    x = jnp.asarray(x)
    qd, sd = quantize(x, QuantSpec("int8", "tensor"), axis=None)
    det_bias = float(jnp.mean(dequantize(qd, sd)[1:] - x[1:]))
    assert det_bias == pytest.approx(-frac, abs=1e-3)  # all round down
    qs, ss = quantize_int8_stochastic(x, jax.random.PRNGKey(seed))
    assert float(ss) == pytest.approx(1.0)
    sto_bias = float(jnp.mean(dequantize(qs, ss)[1:] - x[1:]))
    # 6 sigma of a Bernoulli(frac) mean over n-1 draws
    assert abs(sto_bias) <= 6.0 * np.sqrt(frac * (1 - frac) / (n - 1))
    assert abs(sto_bias) < abs(det_bias) / 2


def test_stochastic_rounding_pure_in_key_and_clipped():
    x = _rand((32, 64), 9, 3.0)
    k0, k1 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    q_a, s_a = quantize_int8_stochastic(x, k0)
    q_b, s_b = quantize_int8_stochastic(x, k0)
    assert np.array_equal(np.asarray(q_a), np.asarray(q_b))
    assert float(s_a) == float(s_b)
    q_c, _ = quantize_int8_stochastic(x, k1)
    assert not np.array_equal(np.asarray(q_a), np.asarray(q_c))
    assert int(jnp.max(q_a)) <= 127 and int(jnp.min(q_a)) >= -127
    # per-axis granularity mirrors `quantize`
    q_t, s_t = quantize_int8_stochastic(x, k0, axis=1)
    assert s_t.shape == (32, 1)
    # reconstruction stays within one scale unit of the input
    err = np.abs(np.asarray(dequantize(q_t, s_t) - x))
    assert (err <= np.asarray(s_t) + 1e-6).all()
