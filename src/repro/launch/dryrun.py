import os

# MUST precede any jax import (jax locks the device count at first init).
# The 512 placeholder host devices exist ONLY for this dry-run process.
# Any inherited device-count flag (e.g. the CI 8-device matrix leg) is
# stripped first: XLA resolves duplicate flags last-wins, so a leftover
# "=8" after our 512 would silently shrink the production mesh.
_inherited = [f for f in os.environ.get("XLA_FLAGS", "").split()
              if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    ["--xla_force_host_platform_device_count=512"] + _inherited
)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs abstract params/optimizer/batch specs (no allocation),
  3. jit(step, in_shardings, out_shardings).lower(...).compile(),
  4. records memory_analysis(), cost_analysis(), and the collective-byte
     census parsed from the optimized HLO, into a JSON file consumed by
     benchmarks/roofline_report.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out experiments/dryrun]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from ..configs import ARCH_IDS, SHAPES, cell_applicable, get_config
from ..core.hlo_census import census
from ..core.roofline import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, RooflineReport
from ..core.precision import resolve_precision
from ..core.transfer_model import (
    AbftGemm, GemmProblem, PagedKVDecode, PallasGemmTiling,
    RingCollectiveGemm, SharedPrefixPrefill, SparseGemm,
)
from ..launch.mesh import make_production_mesh
from ..launch.specs import cell_specs
from ..launch.steps import make_prefill_step, make_serve_step, make_train_step
from ..models import build_model
from ..optim.adamw import AdamW
from ..optim.schedules import warmup_cosine
from ..parallel.sharding import autotune_collective_policy, make_rules, use_rules


def collective_gemm_reports(cfg, mesh, tokens_per_step: int) -> dict:
    """Per-layer overlap model for the TP ring collective GEMMs: one record
    per projection kind (qkv / attn-out / mlp-up / mlp-down / lm_head) with
    exposed-comm bytes/time from `transfer_model.RingCollectiveGemm`.

    Activations are modeled in bf16 (elem_bytes=2), matching the roofline's
    PEAK_FLOPS_BF16 operating point.  A gated (SwiGLU) up projection runs
    TWO chunk GEMMs per ring hop (up + gate) against the same streamed x
    chunk — modeled as a doubled-N problem: compute doubles, comm doesn't."""
    P = int(mesh.shape.get("model", 1))
    if P <= 1:
        return {}
    dp = max(mesh.size // P, 1)
    M = max(tokens_per_step // dp, 1)  # rows entering each TP ring
    d, hd = cfg.d_model, cfg.hd
    ff = cfg.d_ff or 4 * d
    up_n = 2 * ff if cfg.activation == "silu" else ff  # gate rides the ring
    gemms = {
        "qkv": ("allgather", GemmProblem(M, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd, d, 2)),
        "attn_out": ("reduce_scatter", GemmProblem(M, d, cfg.n_heads * hd, 2)),
        "mlp_up": ("allgather", GemmProblem(M, up_n, d, 2)),
        "mlp_down": ("reduce_scatter", GemmProblem(M, d, ff, 2)),
        "lm_head": ("allgather", GemmProblem(M, cfg.vocab, d, 2)),
    }
    # the ring schedule (direction / chunk split) is AUTOTUNED from the
    # same transfer model instead of assuming the bidirectional default;
    # the chosen schedule is logged alongside the per-layer records
    policy, schedule = autotune_collective_policy(
        mesh, gemms.values(), ici_bw=ICI_BW, peak_flops=PEAK_FLOPS_BF16)
    bidir = policy.direction == "bidir"
    out = {"schedule": schedule}
    for name, (mode, prob) in gemms.items():
        ring = RingCollectiveGemm(mode=mode, axis_size=P, bidirectional=bidir)
        out[name] = ring.report(prob, ici_bw=ICI_BW, peak_flops=PEAK_FLOPS_BF16)
    return out


def quantized_gemm_reports(cfg, tokens_per_step: int) -> dict:
    """Per-layer quantized-traffic model for the block projections: one
    record per projection kind with the policy's per-operand HBM bytes and
    the narrow-operand traffic CREDIT vs the bf16 baseline (elem_bytes=2,
    the roofline's operating point).

    ``active`` marks whether the config actually declares the policy
    (cfg.precision != "none"); when it doesn't, the report is the
    counterfactual for the default "int8" policy (weights int8 per-tile,
    activations bf16) so every dryrun spec carries the int8 credit the
    overlap roofline would gain from narrow operands."""
    name = getattr(cfg, "precision", "none")
    active = name not in ("none", "f32")
    prec = resolve_precision(name if active else "int8")
    if prec is None:
        return {}
    M = max(tokens_per_step, 1)
    d, hd = cfg.d_model, cfg.hd
    ff = cfg.d_ff or 4 * d
    gemms = {
        "qkv": (M, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd, d),
        "attn_out": (M, d, cfg.n_heads * hd),
        "mlp_up": (M, 2 * ff if cfg.activation == "silu" else ff, d),
        "mlp_down": (M, d, ff),
    }
    tiling = PallasGemmTiling(128, 128, 128)
    out = {"policy": name if active else "int8", "active": active}
    total_q = total_base = 0
    for gname, (m, n, k) in gemms.items():
        base = GemmProblem(m, n, k, 2)  # bf16 activations & weights
        quant = GemmProblem(m, n, k, prec.a_bytes(2),
                            b_bytes=prec.b_bytes(2), out_bytes=2)
        qb, bb = tiling.hbm_bytes(quant), tiling.hbm_bytes(base)
        total_q += qb
        total_base += bb
        out[gname] = {
            "a_bytes": quant.a_elem_bytes, "b_bytes": quant.b_elem_bytes,
            "out_bytes": quant.out_elem_bytes,
            "hbm_bytes": qb, "hbm_bytes_bf16": bb,
            "traffic_credit_bytes": bb - qb,
            "bytes_ratio": qb / bb if bb else 1.0,
        }
    out["total_hbm_bytes"] = total_q
    out["total_hbm_bytes_bf16"] = total_base
    out["total_traffic_credit_bytes"] = total_base - total_q
    out["bytes_ratio"] = total_q / total_base if total_base else 1.0
    return out


def sparse_gemm_reports(cfg, tokens_per_step: int) -> dict:
    """What 2:4 structured-sparse weights (kernels/sparse, the "sparse24"
    precision policies) would save on this config's block projections: the
    `SparseGemm` stream model at the kernels' default 128x128x128 tiling.

    ``active`` marks whether the config declares a sparse policy
    (cfg.precision naming a registry entry with b_sparse); otherwise the
    report is the counterfactual at the policy's own operand bytes — bf16
    activations/weights for "sparse24", so every dryrun spec carries the
    weight-stream credit turning sparsity on would earn."""
    name = getattr(cfg, "precision", "none")
    prec = resolve_precision(name) if name not in ("none",) else None
    active = prec is not None and prec.b_sparse is not None
    if not active:
        prec = resolve_precision("sparse24")
    M = max(tokens_per_step, 1)
    d, hd = cfg.d_model, cfg.hd
    ff = cfg.d_ff or 4 * d
    gemms = {
        "qkv": (M, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd, d),
        "attn_out": (M, d, cfg.n_heads * hd),
        "mlp_up": (M, 2 * ff if cfg.activation == "silu" else ff, d),
        "mlp_down": (M, d, ff),
    }
    model = SparseGemm(bm=128, bn=128, bk=128)
    out = {"policy": name if active else "sparse24", "active": active}
    total_sparse = total_dense = 0
    for gname, (m, n, k) in gemms.items():
        prob = GemmProblem(m, n, k, prec.a_bytes(2), b_bytes=prec.b_bytes(2),
                           out_bytes=2)
        rec = model.report(prob)
        total_sparse += rec["weight_stream_bytes"]
        total_dense += rec["dense_weight_stream_bytes"]
        out[gname] = rec
    out["total_weight_stream_bytes"] = total_sparse
    out["total_dense_weight_stream_bytes"] = total_dense
    out["weight_ratio"] = (total_sparse / total_dense) if total_dense else 1.0
    return out


def abft_gemm_reports(cfg, tokens_per_step: int) -> dict:
    """What checksummed GEMMs (kernels/abft, ops ``abft=``) would cost on
    this config's block projections: the `AbftGemm` overhead model at the
    kernels' default 128x128 tiling, float-tolerance path (the bf16
    roofline operating point).  Pure counterfactual — ABFT is a dispatch
    flag, not a config property — so every dryrun spec carries the price
    of turning detection on."""
    M = max(tokens_per_step, 1)
    d, hd = cfg.d_model, cfg.hd
    ff = cfg.d_ff or 4 * d
    gemms = {
        "qkv": (M, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd, d),
        "attn_out": (M, d, cfg.n_heads * hd),
        "mlp_up": (M, 2 * ff if cfg.activation == "silu" else ff, d),
        "mlp_down": (M, d, ff),
    }
    model = AbftGemm(bm=128, bn=128, exact=False)
    out = {"bm": 128, "bn": 128, "exact": False}
    macs = extra = 0
    for gname, (m, n, k) in gemms.items():
        prob = GemmProblem(m, n, k, 2)
        rec = model.report(prob)
        macs += prob.macs
        extra += rec["checksum_macs"]
        out[gname] = rec
    out["total_checksum_macs"] = extra
    out["total_overhead_ratio"] = extra / macs if macs else 0.0
    return out


def _paged_attn_layers(cfg) -> int:
    """Attention-block count when the paged serving paths cover `cfg`
    (attention-only segments, no shared block / modality prefix / encoder —
    the `DecoderLM.supports_paged` predicate), else 0.  Gates both paged
    serve reports: pricing a credit the stack cannot realize would misprice
    the serving roofline."""
    paged_capable = (not cfg.shared_attn_every and not cfg.frontend_dim
                     and not cfg.enc_layers
                     and all(kind in ("dense", "moe") for kind, _ in cfg.blocks))
    if not paged_capable:
        return 0
    return sum(n for kind, n in cfg.blocks if kind in ("dense", "moe"))


def paged_kv_decode_reports(cfg, preset, *, page_size: int = 128) -> dict:
    """Decode-step KV traffic model for serve cells: dense (slots, max_len)
    rectangle vs pages actually resident, at representative live-token fill
    ratios.  Cache elements modeled in bf16 (the roofline operating point);
    n_layers counts the attention blocks that hold a KV cache."""
    n_attn = _paged_attn_layers(cfg)
    if not n_attn:
        return {}
    model = PagedKVDecode(
        batch_slots=preset.global_batch,
        max_len=preset.seq_len,
        page_size=page_size,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd,
        n_layers=n_attn,
        kv_bytes=2,
    )
    out = {"page_size": page_size, "n_attn_layers": n_attn, "fills": {}}
    for fill in (0.25, 0.5, 0.75, 1.0):
        lengths = [max(1, int(fill * preset.seq_len))] * preset.global_batch
        out["fills"][f"{fill:.2f}"] = model.report(lengths, hbm_bw=HBM_BW)
    return out


def shared_prefix_reports(cfg, preset, *, page_size: int = 128) -> dict:
    """Prefill FLOPs + HBM bytes a prefix-cache hit saves (serve cells):
    the `SharedPrefixPrefill` model priced at representative prompt-overlap
    fractions of the preset's sequence length, with roofline seconds at the
    PEAK_FLOPS_BF16 / HBM_BW operating point.  Gated on the same
    paged-capable predicate as `paged_kv_decode_reports` — the prefix cache
    lives on the page pool."""
    n_attn = _paged_attn_layers(cfg)
    if not n_attn:
        return {}
    model = SharedPrefixPrefill(
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd,
        n_layers=n_attn,
        gated_mlp=(cfg.activation == "silu"),
        act_bytes=2,
        kv_bytes=2,
        page_size=page_size,
    )
    return model.report(preset.seq_len, overlaps=(0.0, 0.5, 0.9),
                        flops_rate=PEAK_FLOPS_BF16, hbm_bw=HBM_BW)


def lower_cell(arch: str, shape: str, mesh_kind: str, *, extra: dict | None = None):
    """Lower+compile one cell; returns the result record dict."""
    cfg = get_config(arch)
    preset = SHAPES[shape]
    ok, reason = cell_applicable(cfg, preset)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    extra = extra or {}
    if extra.get("cfg"):
        cfg = __import__("dataclasses").replace(cfg, **extra["cfg"])
    rules = make_rules(
        mesh, profile=cfg.parallelism, fsdp=cfg.fsdp,
        seq_parallel=extra.get("seq_parallel", False),
    )
    model = build_model(cfg)
    opt = AdamW(lr=warmup_cosine(3e-4, 100, 10_000))
    specs = cell_specs(cfg, preset, rules, opt=opt)

    if specs.kind == "train":
        step = make_train_step(model, cfg, opt,
                               microbatch=extra.get("microbatch", 1))
    elif specs.kind == "prefill":
        step = make_prefill_step(model, cfg)
    else:
        step = make_serve_step(model, cfg)

    t0 = time.time()
    with use_rules(rules):
        jitted = jax.jit(
            step,
            in_shardings=specs.in_shardings,
            out_shardings=specs.out_shardings,
            donate_argnums=specs.donate_argnums,
        )
        lowered = jitted.lower(*specs.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(mem)  # proves it fits (per-device bytes)
    cost = compiled.cost_analysis() or {}
    print({k: v for k, v in cost.items() if k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()

    # Trip-count-aware census: compiled.cost_analysis() counts while-loop
    # (lax.scan) bodies ONCE — verified in tests/test_hlo_census.py — so for
    # scanned layer stacks it undercounts by ~n_layers.  The census parses
    # the optimized HLO, extracts known_trip_count, and multiplies.
    cen = census(hlo)

    per_dev_flops = float(cen.flops)
    # Memory bytes: XLA's own per-op byte model (operands+results at fusion
    # boundaries) scaled by the trip-count inflation ratio measured on FLOPs
    # (dot FLOPs are fusion-independent, so census/xla flops isolates the
    # while-loop undercount).  The raw instruction-level census overcounts on
    # the CPU backend, whose fusion granularity is far finer than TPU's.
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    trip_ratio = (per_dev_flops / xla_flops) if xla_flops > 0 else 1.0
    per_dev_bytes = xla_bytes * max(trip_ratio, 1.0)
    if per_dev_bytes == 0.0:
        per_dev_bytes = float(cen.memory_bytes)
    per_dev_coll = float(cen.collective_bytes)

    # MODEL_FLOPS: 6*N_active*D for train, 2*N_active*D for inference
    n_active = cfg.n_active_params()
    factor = 6.0 if specs.kind == "train" else 2.0
    model_flops = factor * n_active * specs.tokens_per_step

    report = RooflineReport(
        hlo_flops=per_dev_flops * chips,
        hlo_bytes=per_dev_bytes * chips,
        collective_bytes=per_dev_coll * chips,
        chips=chips,
        model_flops=model_flops,
    )

    record = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "status": "ok",
        "kind": specs.kind,
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            ),
            "fits_v5e_16gb": bool(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes < 16 * 2**30
            ),
        },
        "cost": {
            "per_device_flops": per_dev_flops,
            "per_device_bytes": per_dev_bytes,
            "per_device_collective_bytes": per_dev_coll,
            "collective_ops": cen.collective_count_by_kind,
            "collective_bytes_by_kind": cen.collective_bytes_by_kind,
            "unknown_trip_whiles": cen.unknown_trip_whiles,
            "census_instr_level_bytes": float(cen.memory_bytes),
            "trip_ratio": trip_ratio,
            # raw XLA numbers for comparison (loop bodies counted once):
            "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
            "xla_cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
        },
        "roofline": report.as_dict(),
        "collective_gemms": collective_gemm_reports(
            cfg, mesh, specs.tokens_per_step),
        "quantized_gemms": quantized_gemm_reports(cfg, specs.tokens_per_step),
        "sparse_gemms": sparse_gemm_reports(cfg, specs.tokens_per_step),
        "abft_gemms": abft_gemm_reports(cfg, specs.tokens_per_step),
        "paged_kv_decode": (paged_kv_decode_reports(cfg, preset)
                            if specs.kind == "decode" else {}),
        "shared_prefix_prefill": (shared_prefix_reports(cfg, preset)
                                  if specs.kind == "decode" else {}),
        "n_params": cfg.n_params(),
        "n_active_params": n_active,
        "tokens_per_step": specs.tokens_per_step,
        "dropped_shardings": len(rules.dropped),
    }
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--skip-existing", action="store_true")
    # ---- perf-iteration knobs (§Perf hillclimb) ----
    ap.add_argument("--remat", choices=("full", "dots", "none"), default=None)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--moe-groups", type=int, default=None)
    ap.add_argument("--moe-capacity", type=float, default=None)
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--precision", default=None,
                    help="per-projection quantization policy name "
                         "(core/precision.py registry, e.g. int8)")
    ap.add_argument("--tag", default="", help="suffix for perf-variant files")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                for m in meshes:
                    cells.append((a, s, m))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, m) for m in meshes]

    cfg_over = {}
    if args.remat:
        cfg_over["remat_policy"] = args.remat
    if args.attn_chunk:
        cfg_over["attn_chunk_threshold"] = args.attn_chunk
    if args.moe_groups:
        cfg_over["moe_groups"] = args.moe_groups
    if args.moe_capacity:
        cfg_over["moe_capacity_factor"] = args.moe_capacity
    if args.ssm_chunk:
        cfg_over["ssm_chunk"] = args.ssm_chunk
    if args.precision:
        cfg_over["precision"] = args.precision
    extra = {
        "microbatch": args.microbatch,
        "seq_parallel": args.seq_parallel,
        "cfg": cfg_over,
    }

    failures = 0
    for arch, shape, mesh_kind in cells:
        tag = f"__{args.tag}" if args.tag else ""
        path = out / f"{arch}__{shape}__{mesh_kind}{tag}.json"
        if args.skip_existing and path.exists():
            print(f"[skip-existing] {path}")
            continue
        print(f"=== {arch} × {shape} × {mesh_kind} {tag} ===", flush=True)
        try:
            rec = lower_cell(arch, shape, mesh_kind, extra=extra)
            rec["variant"] = {"tag": args.tag, **extra}
        except Exception as e:  # noqa: BLE001 — record the failure, keep going
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                   "status": "error", "error": f"{type(e).__name__}: {e}"}
            failures += 1
        path.write_text(json.dumps(rec, indent=2, default=str))
        if rec["status"] == "ok":
            r = rec["roofline"]
            print(f"  bound={r['bound']} compute={r['compute_s']:.4f}s "
                  f"mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
                  f"exposed_coll={r['exposed_collective_s']:.4f}s "
                  f"overlapped_lb={r['overlapped_step_lb_s']:.4f}s "
                  f"fits={rec['memory']['fits_v5e_16gb']} "
                  f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)",
                  flush=True)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
