"""Overlapped-vs-serialized collective GEMM benchmark on a CPU host mesh.

Runs the ring all-gather⊗matmul and matmul⊗reduce-scatter paths
(kernels/mx_collective_matmul) against their serialized references
(all-gather-then-matmul / matmul-then-psum) on an 8-device
`--xla_force_host_platform_device_count` mesh, checks numerics, and
writes the machine-readable ``BENCH_collective.json`` artifact so the
perf trajectory is comparable across PRs.

Host-mesh caveat (same as kernel_bench): all "devices" share the host
CPU, so these are *structural* wins — the ring moves P× less data per
hop than the serialized collective materializes (reduce-scatter ships
(M/P,N) partials instead of psum'ing the full (M,N); the all-gather
ring streams chunks through cache instead of materializing the full
(M,K) per device) — not ICI-overlap wins, which the analytical model
(`transfer_model.RingCollectiveGemm`) covers.

MUST be run as its own process (python -m benchmarks.collective_bench):
the device-count flag only takes effect before jax initializes, so no
parent that has started JAX can run it as a child.
"""
from __future__ import annotations

import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_collective.json"

# Shapes chosen where the structural win is visible on a shared-CPU mesh:
# the all-gather ring wants a K-heavy problem (serialized materializes the
# full M×K per device), the reduce-scatter ring an N-heavy one (serialized
# psums the full M×N).
AG_SHAPE = (2048, 4096, 1024)  # M, K, N
RS_SHAPE = (2048, 1024, 2048)
ITERS = 3


def _time(fn, *args, iters=ITERS):
    fn(*args).block_until_ready()  # compile + warm
    total = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        total += time.perf_counter() - t0
    return total / iters * 1e6  # us


def run(out_path=DEFAULT_OUT) -> list[tuple[str, float, str]]:
    from repro.core.roofline import ICI_BW, PEAK_FLOPS_BF16
    from repro.core.transfer_model import GemmProblem, RingCollectiveGemm
    from repro.kernels.mx_collective_matmul import (
        ChunkCompute,
        ring_allgather_matmul,
        ring_matmul_reduce_scatter,
        serialized_allgather_matmul,
        serialized_matmul_psum,
    )
    from repro.kernels.mx_matmul import Epilogue
    from repro.launch.mesh import make_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        return [("collective_bench_skipped", 0.0, f"devices={n_dev}")]
    mesh = make_mesh((1, n_dev), ("data", "model"))
    cc = ChunkCompute(backend="xla")
    ep = Epilogue()
    rows: list[tuple[str, float, str]] = []
    record: dict = {"device_count": n_dev, "iters": ITERS, "modes": {}}

    def sm(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    # ---- all-gather ⊗ matmul ----
    M, K, N = AG_SHAPE
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    specs = ((P("model", None), P(None, "model")), P(None, "model"))
    variants = {}
    for d in ("fwd", "bidir"):
        variants[f"ring_{d}"] = sm(
            lambda xs, ws, d=d: ring_allgather_matmul(
                xs, ws, axis_name="model", axis_size=n_dev, compute=cc,
                epilogue=ep, out_dtype=jnp.float32, direction=d),
            *specs)
    variants["serialized"] = sm(
        lambda xs, ws: serialized_allgather_matmul(
            xs, ws, axis_name="model", compute=cc, epilogue=ep,
            out_dtype=jnp.float32),
        *specs)
    ref = variants["serialized"](x, w)
    ag: dict = {"shape": {"M": M, "K": K, "N": N}, "us": {}}
    for name, f in variants.items():
        err = float(jnp.abs(f(x, w) - ref).max())
        assert err < 1e-3, f"allgather {name} numerics off: {err}"
        us = _time(f, x, w)
        ag["us"][name] = us
        rows.append((f"collective_ag_{name}", us, f"M{M}K{K}N{N}"))
    best = min(ag["us"]["ring_fwd"], ag["us"]["ring_bidir"])
    ag["speedup_vs_serialized"] = ag["us"]["serialized"] / best
    ag["overlap_model"] = RingCollectiveGemm("allgather", n_dev).report(
        GemmProblem(M, N, K, 4), ici_bw=ICI_BW, peak_flops=PEAK_FLOPS_BF16)
    record["modes"]["allgather"] = ag
    rows.append(("collective_ag_speedup", ag["speedup_vs_serialized"],
                 "ring_vs_allgather_then_matmul"))

    # ---- matmul ⊗ reduce-scatter ----
    M, K, N = RS_SHAPE
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    specs = ((P(None, "model"), P("model", None)), P("model", None))
    variants = {}
    for d in ("fwd", "bidir"):
        variants[f"ring_{d}"] = sm(
            lambda xs, ws, d=d: ring_matmul_reduce_scatter(
                xs, ws, axis_name="model", axis_size=n_dev, compute=cc,
                epilogue=ep, out_dtype=jnp.float32, direction=d),
            *specs)
    variants["serialized"] = sm(
        lambda xs, ws: serialized_matmul_psum(
            xs, ws, axis_name="model", axis_size=n_dev, compute=cc,
            epilogue=ep, out_dtype=jnp.float32),
        *specs)
    ref = variants["serialized"](x, w)
    rs: dict = {"shape": {"M": M, "K": K, "N": N}, "us": {}}
    for name, f in variants.items():
        err = float(jnp.abs(f(x, w) - ref).max())
        assert err < 1e-2, f"reduce_scatter {name} numerics off: {err}"
        us = _time(f, x, w)
        rs["us"][name] = us
        rows.append((f"collective_rs_{name}", us, f"M{M}K{K}N{N}"))
    best = min(rs["us"]["ring_fwd"], rs["us"]["ring_bidir"])
    rs["speedup_vs_serialized"] = rs["us"]["serialized"] / best
    rs["overlap_model"] = RingCollectiveGemm("reduce_scatter", n_dev).report(
        GemmProblem(M, N, K, 4), ici_bw=ICI_BW, peak_flops=PEAK_FLOPS_BF16)
    record["modes"]["reduce_scatter"] = rs
    rows.append(("collective_rs_speedup", rs["speedup_vs_serialized"],
                 "ring_vs_matmul_then_psum"))

    record["overlapped_beats_serialized"] = bool(
        ag["speedup_vs_serialized"] > 1.0 or rs["speedup_vs_serialized"] > 1.0
    )
    if out_path:
        Path(out_path).write_text(json.dumps(record, indent=2))
        rows.append(("collective_bench_artifact", 0.0, str(out_path)))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="path for the BENCH_collective.json artifact")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(args.out):
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
