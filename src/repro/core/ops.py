"""MX dispatch layer: every heavy matmul in the framework routes through here.

`MXPolicy` is the software surface of the paper's `msettile`/`mx*` ISA: it
selects the kernel backend and the tile plan.  Model code calls
`ops.matmul(a, b)` / `ops.linear(x, w, b, activation=...)` /
`ops.grouped_matmul(x, w, sizes)`; which physical kernel runs is a
deployment decision:

  - "pallas_mx"        — the paper-faithful TPU kernel (VMEM accumulator,
                         C-reset, plan from core.tiling, fused epilogue).
                         TPU, or CPU via interpret=True (tests).
  - "pallas_baseline"  — the paper's baseline traffic pattern (no inter-k
                         buffering, unfused epilogue), for A/B comparisons.
  - "xla"              — plain jnp ops and the `kernels/ref.py` oracles:
                         the CPU path (tests, dry-run lowering — Pallas TPU
                         kernels cannot lower on the CPU backend) and the
                         reference the kernels are checked against.

The default is not a flag: `MXPolicy()` takes the platform's backend
(`default_backend()`), the compiled Pallas kernels on a TPU and the XLA
path elsewhere, so CPU tests keep their semantics and a TPU never silently
runs the references.  Interpret mode exists only off-TPU.

Tile plans are cached per unique (policy, M, N, K, per-operand bytes): the
planner's O(candidates³) search would otherwise rerun on every un-jitted
call (`plan_cache_info()` exposes hit/miss counters for tests/benchmarks).

Mixed precision threads through here as ONE object: `core.precision.
PrecisionPolicy` (explicit `precision=` argument or the `use_precision()`
context) decides what the operands look like in HBM (int8/fp8 payloads +
scales, or bf16 casts), and this layer quantizes once, plans with
per-operand element sizes, and hands the scales to whichever kernel wins
the dispatch — plain, grouped, or ring collective.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import abft as abft_mod
from ..kernels.abft import AbftConfig, SDCError
from ..kernels.baseline_matmul import baseline_matmul
from ..kernels.mx_grouped_matmul import (
    grouped_matmul_reference,
    mx_grouped_matmul,
)
from ..kernels.mx_matmul import Epilogue, apply_epilogue, dot_f32, mx_matmul_fused
from ..kernels.quant import dequantize, quantize_operand
from ..kernels.sparse import compress_24, expand_24, prune_24
from .precision import (
    PrecisionPolicy,
    current_precision,
    resolve_precision,
)
from .tiling import DEFAULT_VMEM_BUDGET, TilePlan, plan_matmul_tiles
from .transfer_model import GemmProblem

BACKENDS = ("xla", "pallas_mx", "pallas_baseline")
TP_MODES = ("allgather", "reduce_scatter")


@functools.lru_cache(maxsize=1024)
def _cached_plan(
    policy: "MXPolicy", M: int, N: int, K: int, elem_bytes: int,
    fused_epilogue_ops: int, b_bytes: Optional[int] = None,
    out_bytes: Optional[int] = None, b_sparse: bool = False,
    gated: bool = False,
) -> TilePlan:
    """The planner runs once per unique (policy, M, N, K, per-operand
    bytes, gated) key; MXPolicy is a frozen dataclass, so it hashes by value.
    ``elem_bytes`` is the A-operand element size; quantized GEMMs key on
    their narrow b_bytes/out_bytes too, so an int8-weights plan never
    collides with the f32 plan for the same shape — and ``b_sparse`` keys
    2:4-compressed weight streams (fractional bytes/elem) separately."""
    if policy.bm and policy.bn and policy.bk:
        from .transfer_model import PallasGemmTiling

        t = PallasGemmTiling(policy.bm, policy.bn, policy.bk,
                             accumulate_in_vmem=policy.backend != "pallas_baseline",
                             fused_epilogue_ops=fused_epilogue_ops,
                             gated=gated)
        p = GemmProblem(M, N, K, elem_bytes, b_bytes=b_bytes,
                        out_bytes=out_bytes, b_sparse=b_sparse)
        return TilePlan(
            policy.bm, policy.bn, policy.bk,
            hbm_bytes=t.hbm_bytes(p),
            vmem_bytes=t.vmem_bytes(p),
            arithmetic_intensity=t.arithmetic_intensity(p),
            grid_steps=t.grid_steps(p),
            accumulate_in_vmem=t.accumulate_in_vmem,
            epilogue_saved_bytes=t.epilogue_saved_bytes(p),
        )
    return plan_matmul_tiles(
        GemmProblem(M, N, K, elem_bytes, b_bytes=b_bytes,
                    out_bytes=out_bytes, b_sparse=b_sparse),
        vmem_budget=policy.vmem_budget,
        accumulate_in_vmem=policy.backend != "pallas_baseline",
        fused_epilogue_ops=fused_epilogue_ops,
        gated=gated,
    )


def plan_cache_info():
    """(hits, misses, maxsize, currsize) of the tile-plan cache."""
    return _cached_plan.cache_info()


def plan_cache_clear() -> None:
    _cached_plan.cache_clear()


def default_backend() -> str:
    """The kernel backend this platform runs: the Pallas MX kernels on a
    TPU, the XLA path everywhere else."""
    return "pallas_mx" if jax.default_backend() == "tpu" else "xla"


@dataclasses.dataclass(frozen=True)
class MXPolicy:
    backend: str = ""  # "" = the platform's: default_backend()
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    # None = interpret exactly when not on a TPU (the Pallas interpreter is
    # the kernels' CPU path); a TPU refuses interpret mode.
    interpret: Optional[bool] = None
    # Fixed block shapes override the tile planner when set:
    bm: Optional[int] = None
    bn: Optional[int] = None
    bk: Optional[int] = None

    def __post_init__(self):
        if not self.backend:
            object.__setattr__(self, "backend", default_backend())
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; one of {BACKENDS}")
        on_tpu = jax.default_backend() == "tpu"
        if self.interpret is None:
            object.__setattr__(self, "interpret", not on_tpu)
        elif self.interpret and on_tpu:
            raise ValueError("interpret mode is the kernels' CPU path; on a "
                             "TPU they run compiled (interpret=False)")

    def plan(
        self, M: int, N: int, K: int, elem_bytes: int,
        fused_epilogue_ops: int = 0, *,
        b_bytes: Optional[int] = None, out_bytes: Optional[int] = None,
        b_sparse: bool = False, gated: bool = False,
    ) -> TilePlan:
        """Tile plan for one GEMM.  ``elem_bytes`` is the A-operand element
        size (and the default for B/out); mixed-precision callers pass
        per-operand ``b_bytes`` / ``out_bytes`` so the plan's traffic model
        reports the quantized bytes and the LRU key separates policies.
        ``b_sparse`` prices the weight stream as a 2:4 compressed payload
        + metadata (b_bytes/2 + 0.125 per dense element); ``gated``
        budgets the SwiGLU launch's second weight block and accumulator."""
        return _cached_plan(self, M, N, K, elem_bytes, fused_epilogue_ops,
                            b_bytes, out_bytes, b_sparse, gated)


_state = threading.local()


def current_policy() -> MXPolicy:
    return getattr(_state, "policy", None) or MXPolicy()


@contextlib.contextmanager
def use_policy(policy: MXPolicy):
    prev = getattr(_state, "policy", None)
    _state.policy = policy
    try:
        yield policy
    finally:
        _state.policy = prev


def _flatten_leading(a: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    lead = a.shape[:-2] if a.ndim > 2 else ()
    return a.reshape(-1, a.shape[-1]), lead


def _ambient_precision(precision) -> Optional[PrecisionPolicy]:
    """Explicit per-call precision (policy object or registry name) wins;
    otherwise the use_precision() context; otherwise None (no quant).
    Both None and "none" resolve to no-declaration and FALL THROUGH to the
    ambient context (so config/module defaults don't shadow it); the "f32"
    registry entry is a real identity policy and therefore overrides."""
    resolved = resolve_precision(precision) if precision is not None else None
    return resolved if resolved is not None else current_precision()


def _effective_precision(prec, a_dtype, b_dtype) -> Optional[PrecisionPolicy]:
    """Drop policies that would be the identity for these operand dtypes,
    so the f32/none registry entries cost exactly nothing."""
    if prec is not None and prec.is_noop_for(a_dtype, b_dtype):
        return None
    return prec


def _resolve_abft(abft) -> Optional[AbftConfig]:
    """Per-call ``abft=`` wins (True -> defaults, False -> force off, or an
    explicit AbftConfig); otherwise the ambient use_abft() context.  ABFT
    rides the Pallas fused write-back, so it engages only on the pallas_mx
    backend — the xla/baseline reference paths have no single write-back
    to verify in."""
    if abft is False:
        return None
    if abft is None:
        return abft_mod.current_abft()
    if abft is True:
        return AbftConfig()
    return abft


def _pad_rc(arr, r: int, c: int):
    """Zero-pad a 2-D array up to (r, c) — the same zero padding _pad_to
    applies inside the kernel wrappers, so a tile recompute sees exactly
    the padded blocks the full launch saw (bitwise-identical FMA stream)."""
    pr, pc = r - arr.shape[0], c - arr.shape[1]
    if pr or pc:
        arr = jnp.pad(arr, ((0, pr), (0, pc)))
    return arr


def _abft_fused_gemm(x2, w, *, ep, bias, residual, w_gate, a_s, b_s, bg_s,
                     plan, out_dtype, interpret, cfg: AbftConfig):
    """One checksummed fused GEMM + the recovery protocol.

    The kernel verifies every output tile inside its final-k write-back
    and returns a (grid_m, grid_n) flag map.  Eagerly, flagged tiles are
    localized and recomputed ALONE — the re-launch slices the padded
    operand panels for just that tile, runs the identical (bm, bn, nk)
    program, and is therefore bitwise equal to what the fault-free launch
    would have written — with ``cfg.max_retries`` attempts before the
    typed SDCError.  Under a jit trace there is no host to localize on:
    recovery is a lax.cond that re-runs the clean GEMM iff any tile
    flagged (the common flag-free case pays only the compare).

    ``cfg.fault`` (tests / chaos) injects a transient corruption into the
    first attempt's write-back; retries always run clean."""
    M, K = x2.shape
    N = w.shape[-1]
    bm_, bn_ = min(plan.bm, M), min(plan.bn, N)
    gm, gn = -(-M // bm_), -(-N // bn_)
    spec = abft_mod.make_abft_spec(x2.dtype, w.dtype, K, bm_, bn_)
    base_kw = dict(epilogue=ep, b_gate=w_gate, bias=bias, residual=residual,
                   a_scale=a_s, b_scale=b_s, bg_scale=bg_s,
                   bm=plan.bm, bn=plan.bn, bk=plan.bk,
                   out_dtype=out_dtype, interpret=interpret)
    call_spec, fault_kw = spec, {}
    if cfg.fault is not None:
        fd, fr, fc = abft_mod.build_fault_operands(cfg.fault, gm, gn, bm_, bn_)
        call_spec = spec.with_inject(True)
        fault_kw = dict(fault_delta=fd, fault_row=fr, fault_col=fc)
    out, flags = mx_matmul_fused(x2, w, abft=call_spec, **fault_kw, **base_kw)

    if isinstance(flags, jax.core.Tracer):
        # In-graph recovery: no host, no counters — just the cond.  The
        # clean branch re-runs the whole GEMM, and only executes when a
        # tile actually flagged.
        def _clean():
            return mx_matmul_fused(x2, w, abft=spec, **base_kw)[0]

        return jax.lax.cond(jnp.any(flags > 0), _clean, lambda: out)

    abft_mod._bump("gemms_verified")
    flagged = [(int(i), int(j)) for i, j in np.argwhere(np.asarray(flags) > 0)]
    if not flagged:
        return out
    abft_mod._bump("tiles_flagged", len(flagged))
    n_bad = len(flagged)
    for _attempt in range(cfg.max_retries):
        still = []
        for ti, tj in flagged:
            r0, c0 = ti * bm_, tj * bn_
            r1, c1 = min(r0 + bm_, M), min(c0 + bn_, N)
            t_out, t_flags = mx_matmul_fused(
                _pad_rc(x2[r0:r1], bm_, K),
                _pad_rc(w[:, c0:c1], K, bn_),
                epilogue=ep,
                b_gate=None if w_gate is None else _pad_rc(w_gate[:, c0:c1], K, bn_),
                bias=None if bias is None else _pad_rc(bias[c0:c1].reshape(1, -1), 1, bn_)[0],
                residual=None if residual is None else _pad_rc(residual[r0:r1, c0:c1], bm_, bn_),
                a_scale=None if a_s is None else _pad_rc(a_s[r0:r1], bm_, 1),
                b_scale=None if b_s is None else _pad_rc(b_s[:, c0:c1], 1, bn_),
                bg_scale=None if bg_s is None else _pad_rc(bg_s[:, c0:c1], 1, bn_),
                bm=bm_, bn=bn_, bk=plan.bk,
                out_dtype=out_dtype, interpret=interpret, abft=spec)
            if int(np.asarray(t_flags)[0, 0]):
                still.append((ti, tj))
                continue
            out = out.at[r0:r1, c0:c1].set(t_out[:r1 - r0, :c1 - c0])
        flagged = still
        if not flagged:
            abft_mod._bump("tiles_recovered", n_bad)
            return out
    abft_mod._bump("sdc_errors")
    raise SDCError(
        f"SDC persisted in {len(flagged)} tile(s) {flagged} after "
        f"{cfg.max_retries} recompute attempt(s)",
        flagged=flagged, attempts=cfg.max_retries)


def _abft_grouped_gemm(x, w, group_sizes, *, activation, w_gate, a_s, b_s,
                       bg_s, plan, out_dtype, interpret, cfg: AbftConfig):
    """Checksummed grouped GEMM + recovery.  The kernel returns a
    (row_tiles, col_tiles) flag map; eagerly, each flagged tile is
    recomputed per OVERLAPPING EXPERT through the plain fused kernel on the
    same (bm, bn, bk) window — the padded x block, the expert's weight
    panel, and the epilogue order are identical to what the grouped launch
    computed, so the recompute is bitwise equal to the fault-free result
    for every valid row.  A flagged tile whose rows belong to no group
    (the zero-filled tail) needs no recompute: its output rows are masked
    to zero regardless of the accumulator.  Traced, recovery is the same
    lax.cond whole-rerun as the plain path."""
    T, K = x.shape
    G, _, N = w.shape
    bm_, bn_ = min(plan.bm, T), min(plan.bn, N)
    n_tiles = (T + (-T) % bm_) // bm_
    grid_n = (N + (-N) % bn_) // bn_
    spec = abft_mod.make_abft_spec(x.dtype, w.dtype, K, bm_, bn_)
    base_kw = dict(w_gate=w_gate, activation=activation,
                   a_scale=a_s, b_scale=b_s, bg_scale=bg_s,
                   bm=plan.bm, bn=plan.bn, bk=plan.bk,
                   out_dtype=out_dtype, interpret=interpret)
    call_spec, fault_kw = spec, {}
    if cfg.fault is not None:
        fd, fr, fc = abft_mod.build_fault_operands(
            cfg.fault, n_tiles, grid_n, bm_, bn_)
        call_spec = spec.with_inject(True)
        fault_kw = dict(fault_delta=fd, fault_row=fr, fault_col=fc)
    out, flags = mx_grouped_matmul(x, w, group_sizes, abft=call_spec,
                                   **fault_kw, **base_kw)

    if isinstance(flags, jax.core.Tracer):
        def _clean():
            return mx_grouped_matmul(x, w, group_sizes, abft=spec,
                                     **base_kw)[0]

        return jax.lax.cond(jnp.any(flags > 0), _clean, lambda: out)

    abft_mod._bump("gemms_verified")
    flagged = [(int(i), int(j)) for i, j in np.argwhere(np.asarray(flags) > 0)]
    if not flagged:
        return out
    abft_mod._bump("tiles_flagged", len(flagged))
    raw = np.asarray(group_sizes).astype(np.int64)
    ends = np.minimum(np.cumsum(raw), T)
    starts = np.minimum(np.cumsum(raw) - raw, T)
    ep = Epilogue(activation=activation, a_scale=a_s is not None,
                  b_scale=b_s is not None)
    n_bad = len(flagged)
    for _attempt in range(cfg.max_retries):
        still = []
        for t, j in flagged:
            r0, c0 = t * bm_, j * bn_
            r1, c1 = min(r0 + bm_, T), min(c0 + bn_, N)
            groups = [g for g in range(G)
                      if max(r0, int(starts[g])) < min(r1, int(ends[g]))]
            ok = True
            for g in groups:
                t_out, t_flags = mx_matmul_fused(
                    _pad_rc(x[r0:r1], bm_, K),
                    _pad_rc(w[g, :, c0:c1], K, bn_),
                    epilogue=ep,
                    b_gate=(None if w_gate is None
                            else _pad_rc(w_gate[g, :, c0:c1], K, bn_)),
                    a_scale=None if a_s is None else _pad_rc(a_s[r0:r1], bm_, 1),
                    b_scale=(None if b_s is None
                             else _pad_rc(b_s[g, :, c0:c1], 1, bn_)),
                    bg_scale=(None if bg_s is None
                              else _pad_rc(bg_s[g, :, c0:c1], 1, bn_)),
                    bm=bm_, bn=bn_, bk=plan.bk,
                    out_dtype=out_dtype, interpret=interpret, abft=spec)
                if int(np.asarray(t_flags)[0, 0]):
                    ok = False
                    break
                g0, g1 = max(r0, int(starts[g])), min(r1, int(ends[g]))
                out = out.at[g0:g1, c0:c1].set(
                    t_out[g0 - r0:g1 - r0, :c1 - c0])
            if not ok:
                still.append((t, j))
        flagged = still
        if not flagged:
            abft_mod._bump("tiles_recovered", n_bad)
            return out
    abft_mod._bump("sdc_errors")
    raise SDCError(
        f"SDC persisted in {len(flagged)} grouped tile(s) {flagged} after "
        f"{cfg.max_retries} recompute attempt(s)",
        flagged=flagged, attempts=cfg.max_retries)


def _prepare_quantized(x, w, w_gate, prec: PrecisionPolicy):
    """Quantize/cast/compress one linear's operands per the policy.
    Returns (qa, a_s, qb, b_s, qg, bg_s, b_meta, bg_meta); scales are None
    for cast-only specs, metas are None for dense policies.  The gate
    weight quantizes under the same spec as w but with its OWN scales
    (independent amax).

    Sparse pipeline order: prune (magnitude, on the ORIGINAL weights) ->
    quantize (per-column scales are constant along K, so pruning commutes
    with dequant) -> compress the QUANTIZED payload, so the wire stream is
    narrow values + 2-bit indices.  When K % 8 != 0 the wire format cannot
    tile; the weights stay dense-masked (meta None) and every backend
    still computes the pruned semantics."""
    b_meta = bg_meta = None
    if prec.b_sparse is not None:
        w = prune_24(w)
        if w_gate is not None:
            w_gate = prune_24(w_gate)
    qa, a_s = quantize_operand(x, prec.a, "a")
    qb, b_s = quantize_operand(w, prec.b, "b")
    qg = bg_s = None
    if w_gate is not None:
        qg, bg_s = quantize_operand(w_gate, prec.b, "b")
    if prec.b_sparse is not None and w.shape[-2] % 8 == 0:
        qb, b_meta = compress_24(qb)
        if qg is not None:
            qg, bg_meta = compress_24(qg)
    return qa, a_s, qb, b_s, qg, bg_s, b_meta, bg_meta


def _expand_sparse(qb, qg, b_meta, bg_meta):
    """Decompress a prepared sparse weight pair back to dense-masked form —
    the unfused oracle path (xla/baseline backends, ABFT recovery, plans
    whose bk can't tile the compressed payload).  Consumes the SAME payload
    the fused kernel would stream, so backends agree bit-for-bit on the
    weight values."""
    qb = expand_24(qb, b_meta)
    if qg is not None:
        qg = expand_24(qg, bg_meta)
    return qb, qg


def _tp_mesh():
    """The active collective policy when it spans several devices, else
    None: the condition under which a Pallas call must be partitioned by
    hand, since XLA cannot partition a Mosaic kernel."""
    from ..parallel.sharding import current_collectives

    coll = current_collectives()
    return coll if coll is not None and coll.axis_size > 1 else None


def per_device(fn, *args, specs=None):
    """Call ``fn`` (which launches Pallas kernels) on every device of the
    active multi-device collective policy, inside ``shard_map``.

    ``specs(axis, size) -> (in_specs, out_specs)`` hands each device its own
    shards — tensor parallelism with no communication inside ``fn``;
    ``specs`` None, or returning None, replicates every operand (XLA
    gathers them first) and computes the whole call on each device.
    Inside, the policy is switched off, so ``fn``'s own dispatch runs the
    single-device kernels.  With no such policy this is ``fn(*args)``."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import collective_policy

    coll = _tp_mesh()
    if coll is None:
        return fn(*args)
    spec = specs(coll.axis, coll.axis_size) if specs is not None else None
    in_specs, out_specs = spec if spec else ((P(),) * len(args), P())

    def local(*shards):
        with jax.named_scope("tp.per_device"), collective_policy(
                coll.mesh, axis=coll.axis, enabled=False):
            return fn(*shards)

    return jax.shard_map(local, mesh=coll.mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)(*args)


def _replicated(call, **operands):
    """`per_device` with every operand replicated; None operands stay out
    of the shard_map and reach ``call`` as None."""
    names = [k for k, v in operands.items() if v is not None]
    return per_device(lambda *arrs: call(**dict(zip(names, arrs))),
                      *(operands[k] for k in names))


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    policy: Optional[MXPolicy] = None,
    out_dtype=None,
    precision=None,
    abft=None,
    b_transposed: bool = False,
) -> jax.Array:
    """D = A @ B through the MX dispatch.  a: (..., M, K), b: (K, N).
    ``precision`` (PrecisionPolicy or registry name; explicit only — the
    ambient use_precision() context applies to linear/grouped_matmul, not
    to raw matmuls) routes through the quantized path.  ``abft`` (config,
    True/False, or None for the ambient use_abft() context) turns on the
    checksummed write-back on the pallas_mx backend.

    ``b_transposed``: b is stored (N, K) and D = A @ B.T — the tied LM
    head over an embedding table.  Both backends fold the transpose into
    the contraction (no (K, N) copy); it takes no precision and no ABFT."""
    policy = policy or current_policy()
    out_dtype = out_dtype or a.dtype
    if policy.backend != "xla" and _tp_mesh() is not None:
        from jax.sharding import PartitionSpec as P

        def call(a, b):
            return matmul(a, b, policy=policy, out_dtype=out_dtype,
                          precision=precision, abft=abft,
                          b_transposed=b_transposed)

        def column_parallel(ax, size):  # the tied head: vocab-sharded table
            if not b_transposed or b.shape[0] % size:
                return None
            return (P(), P(ax, None)), P(*([None] * (a.ndim - 1)), ax)
        return per_device(call, a, b, specs=column_parallel)
    if b_transposed:
        if precision is not None or abft:
            raise ValueError("b_transposed takes no precision and no abft")
        if policy.backend == "xla":
            return jnp.dot(a, b.T, preferred_element_type=jnp.float32
                           ).astype(out_dtype)
        if policy.backend != "pallas_mx":
            return matmul(a, b.T, policy=policy, out_dtype=out_dtype)
    prec = _effective_precision(resolve_precision(precision), a.dtype, b.dtype)
    if prec is not None:
        return linear(a, b, None, policy=policy, out_dtype=out_dtype,
                      precision=prec, abft=abft)
    if policy.backend == "xla":
        return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)

    a2, lead = _flatten_leading(a)
    M, K = a2.shape
    N = b.shape[0 if b_transposed else -1]
    plan = policy.plan(M, N, K, a.dtype.itemsize)
    kw = dict(bm=plan.bm, bn=plan.bn, bk=plan.bk, out_dtype=out_dtype,
              interpret=policy.interpret)
    cfg = None if b_transposed else _resolve_abft(abft)
    if b_transposed:
        out = mx_matmul_fused(a2, b, b_transposed=True, **kw)
    elif policy.backend == "pallas_mx":
        if cfg is not None:
            out = _abft_fused_gemm(
                a2, b, ep=Epilogue(), bias=None, residual=None, w_gate=None,
                a_s=None, b_s=None, bg_s=None, plan=plan,
                out_dtype=out_dtype, interpret=policy.interpret, cfg=cfg)
        else:
            out = mx_matmul_fused(a2, b, **kw)
    else:
        out = baseline_matmul(a2, b, **kw)
    if a.ndim > 2:
        out = out.reshape(*lead, a.shape[-2], N)
    return out


def _collective_linear(
    x, w, b, *, activation, w_gate, residual, out_scale, policy, out_dtype,
    tp_mode, coll, prec=None, abft_cfg: Optional[AbftConfig] = None,
):
    """Route one linear through the overlapped ring collective matmul.

    Returns None when the problem is not eligible (ring size 1, N or K
    not divisible over the ring, gated reduce-scatter, sparse weights) —
    the caller then falls back to the serialized path.  Rows that do not
    divide over the ring are zero-padded onto it and sliced off after.
    Per-shard tile plans come from the same LRU cache as the single-device
    dispatch (keyed on the *chunk* problem).

    Quantization happens ONCE, globally, before shard_map: per-row /
    per-column scales are constant along K, so sharding the narrow payload
    is exact on both ring modes.  On the all-gather ring the per-row scale
    sidecar shards with (and travels alongside) its x chunk; on the
    reduce-scatter ring scales stay device-local and partials travel
    dequantized (see kernels/mx_collective_matmul)."""
    from ..kernels.mx_collective_matmul import ChunkCompute
    from jax.sharding import PartitionSpec as P

    P_ = coll.axis_size
    if P_ <= 1:
        return None
    if prec is not None and prec.b_sparse is not None:
        # Compressed payload/metadata pairs don't shard over the ring yet
        # (the K-sharded reduce-scatter would split metadata bytes across
        # devices); fall back to the serialized sparse path.
        return None
    ax = coll.axis
    x2, lead = _flatten_leading(x)
    M, K = x2.shape
    N = w.shape[-1]
    Mp = M + (-M) % P_  # rows padded onto the ring
    ep = Epilogue(
        activation=activation, bias=b is not None,
        residual=residual is not None, out_scale=out_scale,
    )
    if tp_mode == "allgather":
        # x M-sharded, w/bias N-sharded; output full-M, N-sharded.
        if N % P_:
            return None
        m_loc, n_loc, k_loc = Mp // P_, N // P_, K
        x_spec, w_spec = P(ax, None), P(None, ax)
        b_spec, r_spec = P(ax), P(None, ax)
        as_spec, bs_spec = P(ax, None), P(None, ax)
    else:
        # x K-sharded, w K-sharded; output M-sharded (reduce-scattered).
        if ep.has_gate or K % P_:
            return None
        m_loc, n_loc, k_loc = Mp // P_, N, K // P_
        x_spec, w_spec = P(None, ax), P(ax, None)
        b_spec, r_spec = P(None), P(ax, None)
        as_spec, bs_spec = P(None, None), P(None, None)  # K-invariant scales
    direction = coll.direction
    if direction == "bidir" and m_loc % 2:
        direction = "fwd"  # odd chunk rows cannot split into two half-rings

    a_s = b_s = bg_s = None
    if prec is not None:
        # metas are always None here: sparse policies bailed out above
        x2, a_s, w, b_s, w_gate, bg_s, _, _ = _prepare_quantized(
            x2, w, w_gate, prec)
    if Mp != M:  # zero rows (and zero row scales) produce zero rows
        x2 = _pad_rc(x2, Mp, K)
        a_s = None if a_s is None else _pad_rc(a_s, Mp, 1)

    # the per-*chunk* GEMM plan, LRU-cached like every other dispatch
    a_bytes = x2.dtype.itemsize
    plan = policy.plan(m_loc, n_loc, k_loc, a_bytes,
                       fused_epilogue_ops=ep.n_fused_ops,
                       b_bytes=w.dtype.itemsize,
                       out_bytes=jnp.dtype(out_dtype).itemsize,
                       gated=w_gate is not None)
    cc_abft = None
    fault_t = None
    if abft_cfg is not None and policy.backend == "pallas_mx":
        # Kernel-level checksums for every chunk GEMM; the rings add the
        # traveling-payload sidecar verification on top.
        cc_abft = abft_mod.make_abft_spec(
            x2.dtype, w.dtype, k_loc, min(plan.bm, m_loc), min(plan.bn, n_loc))
        if abft_cfg.fault is not None:
            f = abft_cfg.fault
            # Map the tile fault onto a ring transport fault: the RS ring
            # only receives from step 1 on, the AG ring verifies every step.
            step = (f.tile_i % P_ if tp_mode == "allgather"
                    else 1 + f.tile_i % max(P_ - 1, 1))
            fault_t = (step, int(f.row), int(f.col), float(f.delta))
    cc = ChunkCompute(
        backend="pallas_mx" if policy.backend == "pallas_mx" else "xla",
        bm=plan.bm, bn=plan.bn, bk=plan.bk, interpret=policy.interpret,
        abft=cc_abft,
    )
    res2 = None
    if residual is not None:
        res2 = _pad_rc(jnp.broadcast_to(
            residual, (*lead, x.shape[-2], N) if lead else (M, N)
        ).reshape(M, N), Mp, N)

    in_specs, operands = [x_spec, w_spec], [x2, w]
    if b is not None:
        in_specs.append(b_spec)
        operands.append(b)
    if w_gate is not None:
        in_specs.append(w_spec)  # gate weight shards exactly like w
        operands.append(w_gate)
    if res2 is not None:
        in_specs.append(r_spec)
        operands.append(res2)
    for s, spec in ((a_s, as_spec), (b_s, bs_spec), (bg_s, bs_spec)):
        if s is not None:
            in_specs.append(spec)
            operands.append(s)
    has_bias, has_gate, has_res = (
        b is not None, w_gate is not None, res2 is not None)
    out_spec = P(None, ax) if tp_mode == "allgather" else P(ax, None)
    caller_args = (
        coll.mesh, ax, P_, direction, cc, ep, tp_mode,
        has_bias, has_gate, has_res,
        a_s is not None, b_s is not None, bg_s is not None,
        jnp.dtype(out_dtype).name, tuple(in_specs), out_spec,
    )
    out = _ring_caller(*caller_args, fault_t)(*operands)
    if cc_abft is not None:
        out, nflags = out
        # A clean rerun of the SAME jitted ring executable is deterministic,
        # so recovery is bitwise equal to the fault-free run.
        clean = _ring_caller(*caller_args, None)
        if isinstance(nflags, jax.core.Tracer):
            out = jax.lax.cond(nflags > 0, lambda: clean(*operands)[0],
                               lambda: out)
        else:
            abft_mod._bump("gemms_verified")
            n = int(nflags)
            if n:
                abft_mod._bump("tiles_flagged", n)
                for _attempt in range(abft_cfg.max_retries):
                    out2, nf2 = clean(*operands)
                    if int(np.asarray(nf2)) == 0:
                        abft_mod._bump("tiles_recovered", n)
                        out = out2
                        break
                else:
                    abft_mod._bump("sdc_errors")
                    raise SDCError(
                        f"SDC persisted in {tp_mode} ring collective after "
                        f"{abft_cfg.max_retries} rerun attempt(s)",
                        flagged=(("ring", n),), attempts=abft_cfg.max_retries)
    out = out[:M]
    if x.ndim > 2:
        out = out.reshape(*lead, x.shape[-2], N)
    return out


@functools.lru_cache(maxsize=256)
def _ring_caller(mesh, ax, P_, direction, cc, ep, tp_mode,
                 has_bias, has_gate, has_res, has_as, has_bs, has_bgs,
                 out_dtype_name, in_specs, out_spec, fault=None):
    """Jitted shard_map wrapper for one ring configuration, cached so that
    repeated layers (and eager test calls) reuse one compiled executable
    instead of re-tracing an eager 8-device ring per call.  With
    ``cc.abft`` set the rings return (out, n_flags) — the psum'd flag count
    is replicated, so its out-spec is P()."""
    from jax.sharding import PartitionSpec as P
    from ..kernels.mx_collective_matmul import (
        ring_allgather_matmul,
        ring_matmul_reduce_scatter,
    )

    out_dtype = jnp.dtype(out_dtype_name)

    def shard_fn(x_s, w_s, *rest):
        it = iter(rest)
        b_s = next(it) if has_bias else None
        g_s = next(it) if has_gate else None
        r_s = next(it) if has_res else None
        a_sc = next(it) if has_as else None
        b_sc = next(it) if has_bs else None
        bg_sc = next(it) if has_bgs else None
        kw = dict(axis_name=ax, axis_size=P_, compute=cc, epilogue=ep,
                  bias=b_s, residual=r_s, out_dtype=out_dtype,
                  direction=direction, a_scale=a_sc, b_scale=b_sc,
                  fault=fault)
        # "tp.allgather" / "tp.reduce_scatter": a profile names the ring's
        # device ops (chunk GEMMs, hops) by it
        with jax.named_scope(f"tp.{tp_mode}"):
            if tp_mode == "allgather":
                return ring_allgather_matmul(x_s, w_s, b_gate=g_s,
                                             bg_scale=bg_sc, **kw)
            return ring_matmul_reduce_scatter(x_s, w_s, **kw)

    out_specs = (out_spec, P()) if cc.abft is not None else out_spec
    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False,
    ))


def linear(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    activation: str = "none",
    w_gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    out_scale: Optional[float] = None,
    policy: Optional[MXPolicy] = None,
    out_dtype=None,
    tp_mode: Optional[str] = None,
    precision=None,
    abft=None,
) -> jax.Array:
    """y = act(x @ w + b) [+ residual] [* out_scale] — the fused-epilogue
    entry point.  x: (..., M, K), w: (K, N), b: (N,), residual broadcastable
    to (..., M, N).  activation "swiglu" gates with `w_gate` (K, N):
    y = silu(x @ w_gate) * (x @ w + b).

    On the pallas_mx backend the whole epilogue happens inside the kernel's
    final-k write-back (one M*N store, zero intermediate round-trips); the
    other backends compute the same math unfused (the A/B reference).

    ``precision`` (core.precision: a PrecisionPolicy, a registry name like
    "int8", or None to take the ambient ``use_precision()`` context)
    quantizes/casts the operands before dispatch: narrow payloads move
    through HBM (and the TP ring), the kernel accumulates in f32, and the
    dequant scales apply at the single fused write-back.  Every backend
    sees the SAME quantized values (the xla/baseline path dequantizes
    unfused), so A/B comparisons isolate traffic, not numerics.

    ``tp_mode`` declares how this projection shards under tensor
    parallelism: "allgather" (x sharded on rows, w on columns — qkv/up) or
    "reduce_scatter" (x and w sharded on the contraction — out/down).  When
    a `parallel.sharding.collective_policy` context is active and the
    shapes divide over the ring, the GEMM runs as a communication-
    overlapped ring collective matmul (kernels/mx_collective_matmul)
    instead of a serialized collective around a local GEMM; otherwise the
    flag is inert.

    ``abft`` (kernels/abft.AbftConfig, True/False, or None to take the
    ambient ``use_abft()`` context) verifies the GEMM with checksums fused
    into the write-back on the pallas_mx backend: flagged tiles are
    localized and recomputed (bitwise equal to the fault-free result),
    with a typed SDCError after ``max_retries`` failed recomputes.
    """
    policy = policy or current_policy()
    out_dtype = out_dtype or x.dtype
    prec = _effective_precision(_ambient_precision(precision),
                                x.dtype, w.dtype)
    if prec is not None and prec.out is not None:
        out_dtype = prec.out_jnp_dtype
    if (activation == "swiglu") != (w_gate is not None):
        raise ValueError(
            "w_gate must be given iff activation='swiglu' "
            f"(got activation={activation!r}, w_gate={'set' if w_gate is not None else None})"
        )
    if tp_mode is not None:
        if tp_mode not in TP_MODES:
            raise ValueError(f"unknown tp_mode {tp_mode!r}; one of {TP_MODES}")
        from ..parallel.sharding import current_collectives

        coll = current_collectives()
        if coll is not None:
            out = _collective_linear(
                x, w, b, activation=activation, w_gate=w_gate,
                residual=residual, out_scale=out_scale, policy=policy,
                out_dtype=out_dtype, tp_mode=tp_mode, coll=coll, prec=prec,
                abft_cfg=(_resolve_abft(abft)
                          if policy.backend == "pallas_mx" else None),
            )
            coll.routed[tp_mode, "fallback" if out is None else "ring"] += 1
            if out is not None:
                return out
    if policy.backend != "xla" and _tp_mesh() is not None:
        return _replicated(
            functools.partial(
                linear, activation=activation, out_scale=out_scale,
                policy=policy, out_dtype=out_dtype, precision=precision,
                abft=abft),
            x=x, w=w, b=b, w_gate=w_gate, residual=residual)

    if policy.backend == "pallas_mx":
        x2, lead = _flatten_leading(x)
        M, K = x2.shape
        N = w.shape[-1]
        a_s = b_s = bg_s = None
        b_meta = bg_meta = None
        if prec is not None:
            (x2, a_s, w, b_s, w_gate, bg_s,
             b_meta, bg_meta) = _prepare_quantized(x2, w, w_gate, prec)
        ep = Epilogue(
            activation=activation,
            bias=b is not None,
            residual=residual is not None,
            out_scale=out_scale,
            a_scale=a_s is not None,
            b_scale=b_s is not None,
        )
        plan = policy.plan(M, N, K, x2.dtype.itemsize,
                           fused_epilogue_ops=ep.n_fused_ops,
                           b_bytes=w.dtype.itemsize,
                           out_bytes=jnp.dtype(out_dtype).itemsize,
                           b_sparse=b_meta is not None,
                           gated=w_gate is not None)
        res2 = None
        if residual is not None:
            res2 = jnp.broadcast_to(
                residual, (*lead, x.shape[-2], N) if lead else (M, N)
            ).reshape(M, N)
        cfg = _resolve_abft(abft)
        b_sparse = (b_meta is not None and min(plan.bk, K) % 8 == 0
                    and cfg is None)
        if b_meta is not None and not b_sparse:
            # ABFT recovery re-slices dense weight panels (w[:, c0:c1]),
            # and a non-8-aligned bk can't tile the compressed payload:
            # decompress and run the dense-masked kernel — same math.
            w, w_gate = _expand_sparse(w, w_gate, b_meta, bg_meta)
            b_meta = bg_meta = None
        if cfg is not None:
            out = _abft_fused_gemm(
                x2, w, ep=ep, bias=b, residual=res2, w_gate=w_gate,
                a_s=a_s, b_s=b_s, bg_s=bg_s, plan=plan,
                out_dtype=out_dtype, interpret=policy.interpret, cfg=cfg)
        else:
            out = mx_matmul_fused(
                x2, w, epilogue=ep, b_gate=w_gate, bias=b, residual=res2,
                a_scale=a_s, b_scale=b_s, bg_scale=bg_s,
                b_sparse=b_sparse, b_meta=b_meta, bg_meta=bg_meta,
                bm=plan.bm, bn=plan.bn, bk=plan.bk,
                out_dtype=out_dtype, interpret=policy.interpret,
            )
        if x.ndim > 2:
            out = out.reshape(*lead, x.shape[-2], N)
        return out

    # Unfused reference composition (xla / pallas_baseline): each epilogue
    # step is its own op — the M*N round-trips the fused path eliminates.
    if prec is not None:
        # Quantized reference: the SAME narrow payloads the kernel loads,
        # dot'd through the same dot_f32 accumulation, dequantized unfused.
        # Sparse payloads decompress through the shared expand oracle first
        # (same wire bytes, unfused expansion).
        qa, a_s, qb, b_s, qg, bg_s, b_meta, bg_meta = _prepare_quantized(
            x, w, w_gate, prec)
        if b_meta is not None:
            qb, qg = _expand_sparse(qb, qg, b_meta, bg_meta)
        y = dot_f32(qa, qb)
        gate = dot_f32(qa, qg) if activation == "swiglu" else None
        ep = Epilogue(activation=activation, bias=b is not None,
                      residual=residual is not None, out_scale=out_scale,
                      a_scale=a_s is not None, b_scale=b_s is not None)
        return apply_epilogue(y, ep, bias=b, gate=gate, residual=residual,
                              a_scale=a_s, b_scale=b_s, bg_scale=bg_s,
                              out_dtype=out_dtype)
    y = matmul(x, w, policy=policy, out_dtype=jnp.float32)
    gate = (matmul(x, w_gate, policy=policy, out_dtype=jnp.float32)
            if activation == "swiglu" else None)
    ep = Epilogue(activation=activation, bias=b is not None,
                  residual=residual is not None, out_scale=out_scale)
    return apply_epilogue(y, ep, bias=b, gate=gate, residual=residual,
                          out_dtype=out_dtype)


def grouped_matmul(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    *,
    activation: str = "none",
    w_gate: Optional[jax.Array] = None,
    policy: Optional[MXPolicy] = None,
    out_dtype=None,
    precision=None,
    abft=None,
) -> jax.Array:
    """Ragged grouped GEMM: out[t] = act(x[t] @ w[g(t)]) for rows sorted by
    group.  x: (T, K), w: (G, K, N), group_sizes: (G,).  One kernel launch
    for all groups on the Pallas path (vs a Python loop of per-group GEMMs).

    ``precision`` (explicit or the ambient use_precision() context)
    quantizes x per token row and w PER EXPERT per output column; the
    (G, 1, N) weight scales are steered to the write-back by the same
    group-offset scalar-prefetch maps as the expert weight blocks.

    ``abft`` (config, True/False, or None for the ambient use_abft()
    context): per-expert checksummed write-back on the pallas_mx backend,
    with flagged tiles recomputed per overlapping expert (bitwise equal to
    the fault-free launch) and a typed SDCError after ``max_retries``.
    """
    policy = policy or current_policy()
    out_dtype = out_dtype or x.dtype
    if policy.backend != "xla" and _tp_mesh() is not None:
        return _replicated(
            functools.partial(
                grouped_matmul, activation=activation, policy=policy,
                out_dtype=out_dtype, precision=precision, abft=abft),
            x=x, w=w, group_sizes=group_sizes, w_gate=w_gate)
    prec = _effective_precision(_ambient_precision(precision),
                                x.dtype, w.dtype)
    if prec is not None and prec.out is not None:
        out_dtype = prec.out_jnp_dtype
    a_s = b_s = bg_s = None
    b_meta = bg_meta = None
    if prec is not None:
        (x, a_s, w, b_s, w_gate, bg_s,
         b_meta, bg_meta) = _prepare_quantized(x, w, w_gate, prec)
    if policy.backend in ("xla", "pallas_baseline"):
        if prec is not None:
            if b_meta is not None:
                # shared expand oracle: same wire payload, unfused
                w, w_gate = _expand_sparse(w, w_gate, b_meta, bg_meta)
            # dequantized reference over the SAME narrow payloads
            x = dequantize(x, a_s) if a_s is not None else x
            w = dequantize(w, b_s) if b_s is not None else w
            if w_gate is not None and bg_s is not None:
                w_gate = dequantize(w_gate, bg_s)
        return grouped_matmul_reference(
            x, w, group_sizes, w_gate=w_gate, activation=activation,
            out_dtype=out_dtype,
        )
    T, K = x.shape
    N = w.shape[-1]
    # Plan for the average per-group problem; the kernel's grid covers the
    # ragged total with the same block shapes.  Credit the fused activation
    # through the same accounting linear() uses.
    G = max(int(w.shape[0]), 1)
    n_fused = Epilogue(activation=activation, a_scale=a_s is not None,
                       b_scale=b_s is not None).n_fused_ops
    plan = policy.plan(max(T // G, 1), N, K, x.dtype.itemsize,
                       fused_epilogue_ops=n_fused,
                       b_bytes=w.dtype.itemsize,
                       out_bytes=jnp.dtype(out_dtype).itemsize,
                       b_sparse=b_meta is not None,
                       gated=w_gate is not None)
    cfg = _resolve_abft(abft)
    b_sparse = (b_meta is not None and min(plan.bk, K) % 8 == 0
                and cfg is None)
    if b_meta is not None and not b_sparse:
        # per-expert ABFT recovery slices dense panels (w[g, :, c0:c1]);
        # decompress and run the dense-masked grouped kernel — same math.
        w, w_gate = _expand_sparse(w, w_gate, b_meta, bg_meta)
        b_meta = bg_meta = None
    if cfg is not None:
        return _abft_grouped_gemm(
            x, w, group_sizes, activation=activation, w_gate=w_gate,
            a_s=a_s, b_s=b_s, bg_s=bg_s, plan=plan, out_dtype=out_dtype,
            interpret=policy.interpret, cfg=cfg)
    return mx_grouped_matmul(
        x, w, group_sizes, w_gate=w_gate, activation=activation,
        a_scale=a_s, b_scale=b_s, bg_scale=bg_s,
        b_sparse=b_sparse, w_meta=b_meta, wg_meta=bg_meta,
        bm=plan.bm, bn=plan.bn, bk=plan.bk,
        out_dtype=out_dtype, interpret=policy.interpret,
    )


# ---------------------------------------------------------------------------
# einsum routing
# ---------------------------------------------------------------------------


def _parse_matmul_subscripts(
    subscripts: str, lhs_ndim: int, rhs_ndim: int
) -> Optional[str]:
    """Structural check: does this einsum reduce to (..., M, K) @ (K, N)?

    Returns the contraction letter when the spec is
        lhs = <leading...> + [k],  rhs = [k, n],  out = <leading...> + [n]
    with no repeated/summed-out leading letters and no ellipsis — i.e. any
    real model contraction like "bsd,df->bsf" or "mk,kn->mn", not just the
    literal "mk,kn" spelling.  Arrow-less specs get einsum's implicit
    output (letters appearing once, alphabetical) before the same check.
    """
    if "." in subscripts:
        return None
    spec = subscripts.replace(" ", "")
    try:
        if "->" in spec:
            ins, out = spec.split("->")
        else:  # implicit output: once-only letters, alphabetical order
            ins = spec
            counts = {}
            for ch in ins.replace(",", ""):
                counts[ch] = counts.get(ch, 0) + 1
            out = "".join(sorted(ch for ch, c in counts.items() if c == 1))
        lhs, rhs = ins.split(",")
    except ValueError:
        return None
    # lhs must be at least (M, K): a 1-D lhs would come back from matmul
    # with a phantom leading dim of 1 instead of the einsum contract's rank.
    if len(lhs) < 2 or len(lhs) != lhs_ndim or len(rhs) != rhs_ndim or len(rhs) != 2:
        return None
    if len(set(lhs)) != len(lhs) or len(set(rhs)) != len(rhs):
        return None
    k, n = rhs[0], rhs[1]
    if not lhs.endswith(k) or k in out or n in lhs:
        return None
    if out != lhs[:-1] + n:
        return None
    return k


def einsum(subscripts: str, *operands, policy: Optional[MXPolicy] = None, **kw):
    """Einsum that routes matmul-shaped contractions through `matmul`;
    everything else falls back to jnp.einsum (still counted by the roofline
    from HLO).  Only `preferred_element_type` is honored on the routed path
    (it becomes the out_dtype; the MX kernel always accumulates in f32);
    any other einsum kwarg (e.g. `precision`) forces the jnp fallback
    rather than being silently dropped."""
    policy = policy or current_policy()
    if policy.backend == "xla" or len(operands) != 2:
        return jnp.einsum(subscripts, *operands, **kw)
    if not set(kw) <= {"preferred_element_type"}:
        return jnp.einsum(subscripts, *operands, **kw)
    lhs_op, rhs_op = operands
    if _parse_matmul_subscripts(subscripts, lhs_op.ndim, rhs_op.ndim):
        return matmul(lhs_op, rhs_op, policy=policy,
                      out_dtype=kw.get("preferred_element_type"))
    return jnp.einsum(subscripts, *operands, **kw)
