"""Timed kernel micro-benchmarks (CPU): MX Pallas (interpret), baseline
Pallas (interpret), and the XLA path, plus the tile-planner itself and the
fused-epilogue / grouped-matmul engines.

interpret-mode timings measure Python-level kernel-body execution — they
validate the traffic/semantics, NOT TPU speed (that's §Roofline's job) —
but the XLA-path numbers are real CPU wall times for the dispatch layer.

Every iteration blocks on its output: without the per-iteration
`block_until_ready`, jax's async dispatch queues all iters and the loop
measures enqueue time, not execution (observed ~10x skew on the XLA rows).

The fusion rows also report *structural* evidence for the epilogue win:
  - kernel-launch census from the jaxpr: the fused Pallas path issues ONE
    pallas_call where the unfused XLA graph issues a dot plus >= 2
    elementwise ops;
  - the transfer-model's epilogue credit: the 2*M*N bytes/op of eliminated
    HBM round-trips (`TilePlan.epilogue_saved_bytes`).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core.ops import (
    MXPolicy,
    grouped_matmul,
    linear,
    matmul,
    plan_cache_clear,
    plan_cache_info,
)
from repro.core.precision import calibrate_static_scale, resolve_precision
from repro.core.tiling import plan_matmul_tiles
from repro.core.transfer_model import GemmProblem
from repro.kernels.quant import executed_gemm_bytes, quantize_operand

BENCH_QUANT_OUT = Path(__file__).resolve().parent.parent / "BENCH_quant.json"

# sweep name -> precision registry name ("int8" sweeps BOTH operands int8:
# the bytes-ratio target is the full narrow-operand credit; the
# weights-only default policy is covered by the "int8_w" alias)
_SWEEP_POLICIES = {
    "f32": None,
    "bf16": "bf16",
    "int8": "int8_all",
    "int8_w": "int8",
    "fp8": "fp8_all",
}


def _time(fn, *args, iters=3):
    fn(*args).block_until_ready()  # compile + warm
    total = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()  # block EVERY iteration (async dispatch)
        total += time.perf_counter() - t0
    return total / iters * 1e6  # us


def _jaxpr_census(fn, *args) -> dict:
    """Count op kinds in the jaxpr — the 'how many kernels / ops' evidence."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    counts: dict = {}

    def walk(jx):
        for eqn in jx.eqns:
            counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)

    walk(jaxpr.jaxpr)
    return counts


_ELEMENTWISE = {
    "add", "mul", "max", "tanh", "logistic", "erf", "div", "sub",
    "integer_pow", "exp",
}


def run() -> list[tuple[str, float, str]]:
    rows = []
    M = K = N = 256
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(2), (N,), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(3), (M, N), jnp.float32)
    flops = 2 * M * N * K

    for backend in ("xla", "pallas_mx", "pallas_baseline"):
        pol = MXPolicy(backend=backend, bm=128, bn=128, bk=64, interpret=True)

        def f(x, y, pol=pol):
            return matmul(x, y, policy=pol)

        us = _time(f, a, b)
        rows.append((f"kernel_{backend}_256", us, f"{flops / us / 1e3:.1f}MFLOP/s_cpu"))

    # ---- fused linear: act(x@w + b) + res in ONE write-back ----
    pol_mx = MXPolicy(backend="pallas_mx", bm=128, bn=128, bk=64, interpret=True)
    pol_xla = MXPolicy(backend="xla")

    def fused(x, y):
        return linear(x, y, bias, activation="gelu", residual=res, policy=pol_mx)

    def unfused(x, y):
        return linear(x, y, bias, activation="gelu", residual=res, policy=pol_xla)

    rows.append(("fused_linear_pallas_256", _time(fused, a, b), "gelu+bias+res"))
    rows.append(("unfused_linear_xla_256", _time(unfused, a, b), "gelu+bias+res"))

    # structural census: fused = one kernel; unfused = dot + elementwise ops
    cf = _jaxpr_census(fused, a, b)
    cu = _jaxpr_census(unfused, a, b)
    n_pallas = cf.get("pallas_call", 0)
    n_dot = cu.get("dot_general", 0)
    n_elem = sum(v for k, v in cu.items() if k in _ELEMENTWISE)
    rows.append((
        "fusion_census",
        float(n_pallas),
        f"fused:{n_pallas}xpallas_call_vs_unfused:{n_dot}xdot+{n_elem}xelemwise",
    ))
    assert n_pallas == 1, f"fused path must be one kernel, got {cf}"
    assert n_dot >= 1 and n_elem >= 2, f"unfused path should show the epilogue ops, got {cu}"

    # transfer-model credit: eliminated M*N epilogue round-trips
    ep_plan = pol_mx.plan(M, N, K, 4, fused_epilogue_ops=3)  # bias+gelu+res
    rows.append((
        "epilogue_traffic_saved_256",
        float(ep_plan.epilogue_saved_bytes),
        f"bytes_saved={ep_plan.epilogue_saved_bytes}"
        f"_vs_gemm={ep_plan.hbm_bytes}",
    ))

    # ---- grouped (MoE) matmul: all experts in one launch ----
    G, C, D, F = 8, 64, 128, 256
    xg = jax.random.normal(jax.random.PRNGKey(4), (G * C, D), jnp.float32)
    wg = jax.random.normal(jax.random.PRNGKey(5), (G, D, F), jnp.float32) * 0.05
    sizes = jnp.full((G,), C, jnp.int32)

    def grouped_pallas(x, w):
        return grouped_matmul(x, w, sizes, policy=pol_mx)

    def grouped_loop(x, w):
        outs = [matmul(x[g * C:(g + 1) * C], w[g], policy=pol_mx) for g in range(G)]
        return jnp.concatenate(outs)

    rows.append(("grouped_matmul_1launch", _time(grouped_pallas, xg, wg),
                 f"{G}experts_x{C}rows"))
    rows.append(("grouped_matmul_Glaunches", _time(grouped_loop, xg, wg),
                 f"{G}experts_loop"))
    cg = _jaxpr_census(grouped_pallas, xg, wg)
    cl = _jaxpr_census(grouped_loop, xg, wg)
    rows.append(("grouped_launch_census", float(cg.get("pallas_call", 0)),
                 f"one_launch:{cg.get('pallas_call', 0)}_vs_loop:{cl.get('pallas_call', 0)}"))

    # ---- tile planner: latency, decision, and the LRU cache ----
    plan_cache_clear()
    t0 = time.perf_counter()
    plan = plan_matmul_tiles(GemmProblem(4096, 53248, 16384, 2))
    us = (time.perf_counter() - t0) * 1e6
    rows.append(("tile_planner_llama_mlp", us,
                 f"bm{plan.bm}_bn{plan.bn}_bk{plan.bk}_AI{plan.arithmetic_intensity:.0f}"))

    pol = MXPolicy(backend="pallas_mx")
    t0 = time.perf_counter()
    pol.plan(4096, 53248, 16384, 2)
    cold = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(100):
        pol.plan(4096, 53248, 16384, 2)
    warm = (time.perf_counter() - t0) / 100 * 1e6
    info = plan_cache_info()
    rows.append(("tile_planner_cached", warm,
                 f"cold{cold:.0f}us_warm{warm:.2f}us_hits{info.hits}"))

    # ---- static calibrated activation scales: the deleted amax reduce ----
    rows.extend(static_scale_rows())

    # ---- quantized dtype sweep + BENCH_quant.json artifact ----
    rows.extend(quant_sweep())
    # The collective GEMM rows need an 8-device host mesh, set before JAX
    # starts: `python -m benchmarks.collective_bench` runs them in a
    # process of their own (the 8-device leg of scripts/run_benches.py).
    return rows


def static_scale_rows(size: int = 256) -> list[tuple[str, float, str]]:
    """Static calibrated activation scales vs dynamic per-call quantization.

    Dynamic int8 activation quantization must read + reduce the whole
    operand (the amax) BEFORE the GEMM can launch — on the serving decode
    path that is an extra pass over the activations every step.  A
    `calibrate_static_scale`'d spec deletes that reduction; the jaxpr
    census counts the disappearing reduce_max ops (the structural
    evidence), the timing rows the wall-clock side, and the error row
    shows calibrated saturation stays within the dynamic path's error
    envelope on in-range data."""
    M = K = N = size
    x = jax.random.normal(jax.random.PRNGKey(6), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(7), (K, N), jnp.float32) * 0.05
    pol = MXPolicy(backend="pallas_mx", bm=128, bn=128, bk=64, interpret=True)
    dyn = resolve_precision("int8_all")
    # calibration pass: a few representative activation batches fix the scale
    calib = [x * 0.7, x, x * 0.9]
    static = dataclasses.replace(dyn, a=calibrate_static_scale(dyn.a, calib))

    def f_dyn(a, b):
        return linear(a, b, policy=pol, out_dtype=jnp.float32, precision=dyn)

    def f_static(a, b):
        return linear(a, b, policy=pol, out_dtype=jnp.float32,
                      precision=static)

    cd = _jaxpr_census(f_dyn, x, w)
    cs = _jaxpr_census(f_static, x, w)
    rd, rs = cd.get("reduce_max", 0), cs.get("reduce_max", 0)
    # the weight operand still reduces in both (quantized per call here;
    # serving quantizes weights once at load) — the activation's reduce is
    # exactly the op that must vanish
    assert rs == rd - 1, (
        f"static activation scale should delete exactly the activation's "
        f"amax reduce: dynamic={rd}, static={rs}")
    ref = jnp.dot(x, w, preferred_element_type=jnp.float32)
    err_d = float(jnp.abs(f_dyn(x, w) - ref).max())
    err_s = float(jnp.abs(f_static(x, w) - ref).max())
    rows = [
        ("static_scale_census", float(rs),
         f"amax_reduces_static:{rs}_vs_dynamic:{rd}"),
        (f"quant_int8_dynamic_scale_{size}", _time(f_dyn, x, w),
         f"err{err_d:.3f}"),
        (f"quant_int8_static_scale_{size}", _time(f_static, x, w),
         f"err{err_s:.3f}"),
    ]
    assert err_s < 10 * max(err_d, 1e-6), (
        f"calibrated static scale error blew up: {err_s} vs dynamic {err_d}")
    return rows


def quant_sweep(
    dtypes=("f32", "bf16", "int8"),
    size: int = 1024,
    tile: int = 256,
    out_path: Path = BENCH_QUANT_OUT,
    iters: int = 3,
) -> list[tuple[str, float, str]]:
    """Dtype sweep over one size³ GEMM through the MX Pallas kernel
    (interpret mode): wall time, max error vs the f32 result, and — the
    point — HBM bytes moved per the PrecisionPolicy's transfer model vs
    the as-executed count derived from the concrete launch
    (kernels.quant.executed_gemm_bytes: padded shapes, payload itemsizes,
    scale sidecars).  Model and measurement must agree within 10% on
    aligned shapes; the JSON artifact records both plus the bytes/speedup
    ratios vs f32 so the narrow-operand credit is tracked across PRs.
    """
    M = N = K = size
    rng_a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    rng_b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32) * 0.05
    pol = MXPolicy(backend="pallas_mx", bm=tile, bn=tile, bk=tile,
                   interpret=True)
    ref = jnp.dot(rng_a, rng_b, preferred_element_type=jnp.float32)
    ref_max = float(jnp.abs(ref).max())

    rows, result = [], {}
    # the f32 baseline is computed unconditionally so the *_vs_f32 fields
    # stay correctly labeled for any --dtypes order/subset
    def f32_call(x, y):
        return linear(x, y, policy=pol, out_dtype=jnp.float32)

    f32_time = _time(f32_call, rng_a, rng_b, iters=iters)
    f32_bytes = pol.plan(M, N, K, 4, b_bytes=4, out_bytes=4).hbm_bytes
    for name in dtypes:
        try:
            policy_name = _SWEEP_POLICIES[name]
        except KeyError:
            raise SystemExit(
                f"unknown sweep dtype {name!r}; one of {tuple(_SWEEP_POLICIES)}"
            ) from None
        prec = resolve_precision(policy_name) if policy_name else None

        def f(x, y, prec=prec):
            return linear(x, y, policy=pol, out_dtype=jnp.float32,
                          precision=prec)

        us = _time(f, rng_a, rng_b, iters=iters)
        err = float(jnp.abs(f(rng_a, rng_b) - ref).max())

        if prec is None:
            qa, a_s, qb, b_s = rng_a, None, rng_b, None
        else:
            qa, a_s = quantize_operand(rng_a, prec.a, "a")
            qb, b_s = quantize_operand(rng_b, prec.b, "b")
        plan = pol.plan(M, N, K, qa.dtype.itemsize,
                        b_bytes=qb.dtype.itemsize, out_bytes=4)
        measured = executed_gemm_bytes(qa, qb, bm=tile, bn=tile, bk=tile,
                                       out_itemsize=4, scales=(a_s, b_s))
        agree = plan.hbm_bytes / measured if measured else 1.0
        result[name] = {
            "policy": policy_name or "f32",
            "a_dtype": str(qa.dtype), "b_dtype": str(qb.dtype),
            "acc_dtype": "float32", "out_dtype": "float32",
            "time_us": us,
            "max_abs_err_vs_f32": err,
            "ref_abs_max": ref_max,
            "model_hbm_bytes": plan.hbm_bytes,
            "executed_hbm_bytes": measured,
            "model_vs_executed": agree,
            "bytes_vs_f32": plan.hbm_bytes / f32_bytes,
            "speedup_vs_f32": f32_time / us if us else 0.0,
        }
        rows.append((f"quant_{name}_{size}", us,
                     f"bytes_x{plan.hbm_bytes / f32_bytes:.2f}"
                     f"_model/measured{agree:.3f}"))
        assert abs(agree - 1.0) < 0.10, (
            f"traffic model disagrees with as-executed bytes for {name}: "
            f"{plan.hbm_bytes} vs {measured}")
    out_path.write_text(json.dumps(
        {"shape": [M, N, K], "tile": [tile, tile, tile],
         "backend": "pallas_mx(interpret)", "dtypes": result}, indent=2))
    rows.append(("quant_artifact", 0.0, f"wrote_{out_path.name}"))
    return rows


def main() -> None:
    """Standalone entry: `python -m benchmarks.kernel_bench --dtypes
    f32,bf16,int8 [--size 1024]` runs ONLY the quantized dtype sweep (the
    CI benchmark hook); with no --dtypes it runs the full row set."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtypes", default=None,
                    help="comma list from " + ",".join(_SWEEP_POLICIES))
    ap.add_argument("--size", type=int, default=1024)
    ap.add_argument("--tile", type=int, default=256)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.dtypes:
        rows = quant_sweep(tuple(d.strip() for d in args.dtypes.split(",")),
                           size=args.size, tile=args.tile)
    else:
        rows = run()
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
