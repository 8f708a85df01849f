"""HLO census: trip-count-aware cost analysis (the correctness layer under
the whole §Roofline deliverable).

The controlled experiments here PROVE the motivating defect: XLA's
compiled.cost_analysis() counts while-loop bodies once, so a 10-step scanned
matmul reports 10% of its FLOPs; the census reports 100%."""
import subprocess
import sys
from pathlib import Path


REPO = Path(__file__).resolve().parents[1]

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.hlo_census import census

N, L = 128, 10

def f(x, ws):
    def body(c, w):
        return c @ w, None
    y, _ = jax.lax.scan(body, x, ws)
    return y

x = jax.ShapeDtypeStruct((N, N), jnp.float32)
ws = jax.ShapeDtypeStruct((L, N, N), jnp.float32)
c = jax.jit(f).lower(x, ws).compile()
expect = L * 2 * N ** 3
xla = c.cost_analysis()["flops"]
cen = census(c.as_text())
assert abs(xla / expect - 0.1) < 0.02, f"xla counted {xla/expect}x (defect changed?)"
assert abs(cen.flops / expect - 1.0) < 0.02, f"census {cen.flops/expect}x"
assert not cen.unknown_trip_whiles

# nested scans
def h(x, ws):
    def outer(c, w):
        def inner(ci, wb):
            return ci @ wb, None
        ci, _ = jax.lax.scan(inner, c, jnp.stack([w, w, w]))
        return ci, None
    y, _ = jax.lax.scan(outer, x, ws)
    return y
c3 = jax.jit(h).lower(x, ws).compile()
r3 = census(c3.as_text())
assert abs(r3.flops / (3 * L * 2 * N ** 3) - 1.0) < 0.02

# sharded: per-device flops + collectives multiplied by trip count
mesh = jax.make_mesh((4,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))
def g(x, ws):
    def body(c, w):
        y = c @ w
        return jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(None, "model"))), None
    y, _ = jax.lax.scan(body, x, ws)
    return jnp.sum(y)
c2 = jax.jit(g, in_shardings=(NamedSharding(mesh, P(None, "model")),
                              NamedSharding(mesh, P(None, "model", None)))).lower(x, ws).compile()
r2 = census(c2.as_text())
assert abs(r2.flops / (expect / 4) - 1.0) < 0.05
ar = r2.collective_count_by_kind["all-reduce"]
assert ar >= L, f"in-loop all-reduces not multiplied: {ar}"
print("CENSUS_OK")
"""


def test_census_fixes_while_loop_undercount():
    import os

    r = subprocess.run(
        [sys.executable, "-c", CODE], capture_output=True, text=True,
        timeout=600, env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO,
    )
    assert "CENSUS_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-2000:]}"


def test_census_on_canned_module():
    from repro.core.hlo_census import census

    hlo = """
HloModule m
%body (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %p = (s32[], f32[64,64]{1,0}) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[64,64]{1,0} get-tuple-element(%p), index=1
  %d = f32[64,64]{1,0} dot(%g1, %g1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,64]{1,0} all-reduce(%d), channel_id=1
  %c1 = s32[] constant(1)
  %a = s32[] add(%g0, %c1)
  ROOT %t = (s32[], f32[64,64]{1,0}) tuple(%a, %ar)
}
%cond (p: (s32[], f32[64,64])) -> pred[] {
  %p = (s32[], f32[64,64]{1,0}) parameter(0)
  %g = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%g, %c), direction=LT
}
ENTRY %main (x: f32[64,64]) -> f32[64,64] {
  %x = f32[64,64]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t = (s32[], f32[64,64]{1,0}) tuple(%c0, %x)
  %w = (s32[], f32[64,64]{1,0}) while(%t), condition=%cond, body=%body
  ROOT %r = f32[64,64]{1,0} get-tuple-element(%w), index=1
}
"""
    r = census(hlo)
    # 7 trips x 2*64^3 flops
    assert r.flops == 7 * 2 * 64**3
    assert r.collective_count_by_kind["all-reduce"] == 7
    assert r.collective_bytes_by_kind["all-reduce"] == 7 * 64 * 64 * 4
    assert not r.unknown_trip_whiles


def test_kernel_launches_counts_pallas_calls_by_name():
    from repro.core.hlo_census import kernel_launches

    hlo = """
  %mx_matmul_fused.3 = f32[8,2048]{1,0:T(8,128)} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/mx_matmul_fused/pallas_call"}
  %mx_matmul_fused.12 = f32[8,512]{1,0:T(8,128)} custom-call(%a, %c), custom_call_target="tpu_custom_call"
  ROOT %mx_flash_decode.1 = f32[8,32,64]{2,1,0} custom-call(%q, %k), custom_call_target="tpu_custom_call"
  %cc = f32[8]{0} custom-call(%x), custom_call_target="Sharding"
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fc
"""
    assert kernel_launches(hlo) == {"mx_matmul_fused": 2, "mx_flash_decode": 1}
