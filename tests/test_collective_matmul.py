"""Communication-overlapped ring collective matmul: numerics vs the
serialized references, epilogue-exactly-once, ops.linear tp_mode dispatch,
model-level equivalence, and the analytical overlap model.

The multi-device checks run in one subprocess on an 8-way virtual host mesh
(XLA_FLAGS must precede jax init, which the in-process suite forbids
changing); the analytical/topology tests run in-process.
"""
import subprocess
import sys

import pytest

from repro.core.transfer_model import GemmProblem, RingCollectiveGemm
from repro.parallel.sharding import CollectivePolicy, collective_policy, \
    current_collectives, ring_topology


# ---------------------------------------------------------------------------
# analytical overlap model (pure python)
# ---------------------------------------------------------------------------


def test_ring_gemm_model_validation():
    with pytest.raises(ValueError):
        RingCollectiveGemm("gather", 8)
    with pytest.raises(ValueError):
        RingCollectiveGemm("allgather", 0)


def test_ring_gemm_comm_volume_and_steps():
    p = GemmProblem(1024, 512, 256, 2)
    ring = RingCollectiveGemm("allgather", 8, bidirectional=False)
    assert ring.steps == 8 and ring.sends == 7
    # each step ships one (M/P, K) chunk of A
    assert ring.chunk_comm_bytes(p) == (1024 // 8) * 256 * 2
    # bidirectional halves the per-link bytes but not the total volume
    bidir = RingCollectiveGemm("allgather", 8, bidirectional=True)
    assert bidir.chunk_comm_bytes(p) == ring.chunk_comm_bytes(p) // 2
    assert bidir.total_comm_bytes(p) == ring.total_comm_bytes(p)
    # reduce-scatter ships f32 partial output chunks
    rs = RingCollectiveGemm("reduce_scatter", 8, bidirectional=False)
    assert rs.chunk_comm_bytes(p) == (1024 // 8) * 512 * 4


def test_exposed_comm_is_max0_comm_minus_compute():
    p = GemmProblem(2048, 2048, 2048, 2)
    ring = RingCollectiveGemm("allgather", 4)
    # compute-rich regime: comm fully hidden
    fast = ring.exposed_comm_s(p, ici_bw=1e12, peak_flops=1e12)
    assert fast == 0.0
    # comm-starved regime: exposure is exactly sends * (comm - compute)
    slow_bw = 1e6
    tc = ring.step_compute_s(p, 1e18)
    tm = ring.step_comm_s(p, slow_bw)
    exposed = ring.exposed_comm_s(p, ici_bw=slow_bw, peak_flops=1e18)
    assert exposed == pytest.approx(ring.sends * (tm - tc))
    assert 0.0 <= ring.overlap_efficiency(
        p, ici_bw=slow_bw, peak_flops=1e18) <= 1.0


def test_overlapped_never_slower_than_serialized():
    p = GemmProblem(4096, 1024, 8192, 2)
    for mode in ("allgather", "reduce_scatter"):
        for P in (2, 4, 8):
            ring = RingCollectiveGemm(mode, P)
            over = ring.overlapped_time_s(p, ici_bw=50e9, peak_flops=197e12)
            ser = ring.serialized_time_s(p, ici_bw=50e9, peak_flops=197e12)
            assert over <= ser + 1e-12
            rep = ring.report(p, ici_bw=50e9, peak_flops=197e12)
            assert rep["exposed_comm_s"] >= 0.0
            assert rep["comm_bytes_total"] > 0


def test_roofline_overlap_credit():
    from repro.core.roofline import RooflineReport

    r = RooflineReport(hlo_flops=1e15, hlo_bytes=1e12, collective_bytes=1e13,
                       chips=8)
    d = r.as_dict()
    assert d["exposed_collective_s"] == pytest.approx(
        max(0.0, r.collective_s - r.compute_s))
    assert d["overlapped_step_lb_s"] <= d["step_lb_s"] + 1e-12
    assert d["overlap_credit_s"] >= 0.0


# ---------------------------------------------------------------------------
# ring topology + policy context (single device OK)
# ---------------------------------------------------------------------------


def test_ring_topology_and_policy_context():
    import numpy as np
    import jax
    from jax.sharding import Mesh

    dev = np.array([jax.devices()[0]] * 4).reshape(1, 4)  # spec-only mesh
    mesh = Mesh(dev, ("data", "model"))
    topo = ring_topology(mesh, "model")
    assert topo["size"] == 4
    assert (0, 1) in topo["fwd"] and (3, 0) in topo["fwd"]
    assert (0, 3) in topo["bwd"] and (1, 0) in topo["bwd"]
    with pytest.raises(ValueError):
        ring_topology(mesh, "expert")

    assert current_collectives() is None
    with collective_policy(mesh, axis="model") as pol:
        assert isinstance(pol, CollectivePolicy)
        assert current_collectives() is pol
        assert pol.axis_size == 4
        with collective_policy(policy=CollectivePolicy(mesh, enabled=False)):
            assert current_collectives() is None  # disabled policy hides
        assert current_collectives() is pol
    assert current_collectives() is None


def test_tp_mode_validation_and_inert_without_policy():
    import jax
    import jax.numpy as jnp
    from repro.core import ops

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    with pytest.raises(ValueError):
        ops.linear(x, w, tp_mode="ring")
    # no collective context: tp_mode is inert, plain dispatch result
    ref = ops.linear(x, w)
    got = ops.linear(x, w, tp_mode="allgather")
    assert jnp.allclose(got, ref)


# ---------------------------------------------------------------------------
# 8-device mesh: numerics + dispatch + model-level (subprocess)
# ---------------------------------------------------------------------------

_MESH_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import ops
from repro.kernels.mx_collective_matmul import (
    ChunkCompute, ring_allgather_matmul, ring_matmul_reduce_scatter,
    serialized_allgather_matmul, serialized_matmul_psum)
from repro.kernels.mx_matmul import Epilogue
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import collective_policy

mesh = make_mesh((1, 8), ("data", "model"))
PZ = 8
M, K, N = 64, 32, 48
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
w = jnp.asarray(rng.normal(size=(K, N)) * 0.1, jnp.float32)
wg = jnp.asarray(rng.normal(size=(K, N)) * 0.1, jnp.float32)
bias = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
res = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
cc = ChunkCompute(backend="xla")

def sm(fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))

# --- all-gather x matmul: both ring directions + bidirectional ---
ep = Epilogue(activation="gelu", bias=True, residual=True)
ref = jax.nn.gelu(x @ w + bias) + res
for d in ("fwd", "bwd", "bidir"):
    got = sm(lambda xs, ws, bs, rs, d=d: ring_allgather_matmul(
                 xs, ws, axis_name="model", axis_size=PZ, compute=cc,
                 epilogue=ep, bias=bs, residual=rs, out_dtype=jnp.float32,
                 direction=d),
             (P("model", None), P(None, "model"), P("model"), P(None, "model")),
             P(None, "model"))(x, w, bias, res)
    assert jnp.allclose(got, ref, atol=2e-4), (d, float(jnp.abs(got-ref).max()))
ser = sm(lambda xs, ws, bs, rs: serialized_allgather_matmul(
             xs, ws, axis_name="model", compute=cc, epilogue=ep, bias=bs,
             residual=rs, out_dtype=jnp.float32),
         (P("model", None), P(None, "model"), P("model"), P(None, "model")),
         P(None, "model"))(x, w, bias, res)
assert jnp.allclose(ser, ref, atol=2e-4)
print("AG_OK")

# swiglu gate rides the ring with the up projection
eps = Epilogue(activation="swiglu")
got = sm(lambda xs, ws, gs: ring_allgather_matmul(
             xs, ws, axis_name="model", axis_size=PZ, compute=cc,
             epilogue=eps, b_gate=gs, out_dtype=jnp.float32, direction="bidir"),
         (P("model", None), P(None, "model"), P(None, "model")),
         P(None, "model"))(x, w, wg)
assert jnp.allclose(got, jax.nn.silu(x @ wg) * (x @ w), atol=2e-4)
print("AG_SWIGLU_OK")

# --- matmul x reduce-scatter: both directions + bidirectional ---
ep2 = Epilogue(bias=True, residual=True)
ref2 = (x @ w + bias) + res
for d in ("fwd", "bwd", "bidir"):
    got = sm(lambda xs, ws, bs, rs, d=d: ring_matmul_reduce_scatter(
                 xs, ws, axis_name="model", axis_size=PZ, compute=cc,
                 epilogue=ep2, bias=bs, residual=rs, out_dtype=jnp.float32,
                 direction=d),
             (P(None, "model"), P("model", None), P(None), P("model", None)),
             P("model", None))(x, w, bias, res)
    assert jnp.allclose(got, ref2, atol=2e-4), (d, float(jnp.abs(got-ref2).max()))
ser = sm(lambda xs, ws, bs, rs: serialized_matmul_psum(
             xs, ws, axis_name="model", axis_size=PZ, compute=cc,
             epilogue=ep2, bias=bs, residual=rs, out_dtype=jnp.float32),
         (P(None, "model"), P("model", None), P(None), P("model", None)),
         P("model", None))(x, w, bias, res)
assert jnp.allclose(ser, ref2, atol=2e-4)
# activation on the reduced sum must see the FULL sum (unfused final path)
ep3 = Epilogue(activation="relu", bias=True)
got = sm(lambda xs, ws, bs: ring_matmul_reduce_scatter(
             xs, ws, axis_name="model", axis_size=PZ, compute=cc,
             epilogue=ep3, bias=bs, out_dtype=jnp.float32, direction="bidir"),
         (P(None, "model"), P("model", None), P(None)),
         P("model", None))(x, w, bias)
assert jnp.allclose(got, jax.nn.relu(x @ w + bias), atol=2e-4)
print("RS_OK")

# --- MX pallas chunk compute inside the ring (interpret mode) ---
ccp = ChunkCompute(backend="pallas_mx", bm=8, bn=16, bk=8, interpret=True)
got = sm(lambda xs, ws, bs, rs: ring_allgather_matmul(
             xs, ws, axis_name="model", axis_size=PZ, compute=ccp,
             epilogue=ep, bias=bs, residual=rs, out_dtype=jnp.float32,
             direction="bidir"),
         (P("model", None), P(None, "model"), P("model"), P(None, "model")),
         P(None, "model"))(x, w, bias, res)
assert jnp.allclose(got, ref, atol=2e-4)
print("PALLAS_RING_OK")

# --- ops.linear dispatch: overlapped == serialized, fallback on misfit ---
with collective_policy(mesh, axis="model"):
    got = ops.linear(x, w, bias, activation="gelu", residual=res,
                     tp_mode="allgather", out_dtype=jnp.float32)
    assert jnp.allclose(got, ref, atol=2e-4)
    got = ops.linear(x, w, bias, residual=res, tp_mode="reduce_scatter",
                     out_dtype=jnp.float32)
    assert jnp.allclose(got, ref2, atol=2e-4)
    x3 = x.reshape(4, 16, K)  # leading batch dims flatten onto the ring
    got = ops.linear(x3, w, bias, tp_mode="allgather", out_dtype=jnp.float32)
    assert jnp.allclose(got, x3 @ w + bias, atol=2e-4)
    got = ops.linear(x[:7], w, bias, tp_mode="allgather",
                     out_dtype=jnp.float32)  # M=7: silent serialized fallback
    assert jnp.allclose(got, x[:7] @ w + bias, atol=2e-4)
    # per-shard plans land in the same LRU cache as plain dispatch
    assert ops.plan_cache_info().currsize > 0
print("DISPATCH_OK")

# --- model level: a full transformer block, overlapped == plain ---
from repro.models.transformer import TransformerBlock
blk = TransformerBlock(d_model=64, n_heads=8, n_kv_heads=8, d_ff=128)
params = blk.init(jax.random.PRNGKey(0))
xb = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
y_plain, _ = blk(params, xb)
with collective_policy(mesh, axis="model"):
    y_coll, _ = blk(params, xb)
assert jnp.allclose(y_coll, y_plain, atol=3e-4), float(jnp.abs(y_coll - y_plain).max())
print("BLOCK_OK")

# --- MoE layer: per-expert overlapped rings, overlapped == plain ---
from repro.models.moe import MoE
moe = MoE(d_model=32, d_ff=64, n_experts=4, top_k=2, n_groups=1)
mp = moe.init(jax.random.PRNGKey(2))
xm = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32), jnp.float32)
ym_plain, aux_p = moe(mp, xm)
with collective_policy(mesh, axis="model"):
    ym_coll, aux_c = moe(mp, xm)
assert jnp.allclose(ym_coll, ym_plain, atol=3e-4)
assert jnp.allclose(aux_c, aux_p, atol=1e-6)
print("MOE_OK")

# --- Pallas on the mesh: XLA cannot partition a Mosaic kernel, so under a
# policy every kernel runs per device — paged decode attention on its own
# heads, the tied head on its vocab shard, a ragged-M projection padded
# onto the ring — and the result equals one device ---
from repro.models.transformer import DecoderLM
from repro.configs import get_config
from repro.parallel.sharding import make_rules, tree_shardings, use_rules
cfg = get_config("llama3.2-1b-smoke")
lm = DecoderLM(cfg)
lp = lm.init(jax.random.PRNGKey(4))
pages = lm.make_paged_cache(9, 4, dtype=jnp.float32)
tok = jnp.asarray([[3], [7]], jnp.int32)
index, lens = jnp.asarray([5, 2], jnp.int32), jnp.asarray([6, 3], jnp.int32)
table = jnp.asarray([[1, 2, 0], [3, 0, 0]], jnp.int32)
pol = ops.MXPolicy(backend="pallas_mx", bm=16, bn=32, bk=16)
mesh4 = make_mesh((1, 4), ("data", "model"))
rules = make_rules(mesh4)
with ops.use_policy(pol):
    want, _ = lm.decode_step_paged(lp, tok, pages, index, table, lens)
    with use_rules(rules), collective_policy(mesh4) as coll:
        lp4 = jax.device_put(lp, tree_shardings(rules, lp, lm.axes()))
        got, _ = jax.jit(lm.decode_step_paged)(lp4, tok, pages, index,
                                               table, lens)
assert jnp.allclose(got, want, atol=2e-4), float(jnp.abs(got - want).max())
assert coll.routed[("allgather", "ring")] and not coll.routed[("allgather", "fallback")]
print("PALLAS_MESH_OK")
print("ALL_COLLECTIVE_OK")
"""


@pytest.mark.slow  # subprocess + 8-device mesh + many shard_map compiles
def test_collective_matmul_on_8device_mesh():
    import os
    import pathlib

    r = subprocess.run(
        [sys.executable, "-c", _MESH_CODE], capture_output=True, text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=pathlib.Path(__file__).resolve().parents[1],
    )
    assert "ALL_COLLECTIVE_OK" in r.stdout, (
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}")
