"""Tile-plan search — the TPU analogue of the paper's `msettile` + §II calculus.

The paper picks (m, n, k, m', n', k') under a 256 B near-FPU buffer budget to
minimize VRF traffic.  We pick Pallas block shapes (bm, bn, bk) under a VMEM
budget to minimize HBM traffic, with MXU alignment constraints (the systolic
array wants multiples of 128 on the matmul dims; the sublane dim wants
multiples of 8 for f32 / 16 for bf16).

`TilePlan` is consumed by `kernels/mx_matmul.py` as its BlockSpec shapes and
by `core/energy.py` / `benchmarks` for the traffic accounting.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, List, Optional, Tuple


from .transfer_model import GemmProblem, PallasGemmTiling

# VMEM a single GEMM launch may claim, counted as Mosaic allocates it
# (kernels/vmem.gemm_vmem_bytes: double-buffered blocks, accumulators, dot
# results).  Half of a TPU v5e core's 128 MiB; the kernel raises its scoped
# limit to match (the compiler's default scope is only 16 MiB).
DEFAULT_VMEM_BUDGET = 64 * 1024 * 1024

MXU_DIM = 128  # systolic array edge
_SUBLANE = {1: 32, 2: 16, 4: 8, 8: 8}  # min second-minor tile per element size


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """A chosen (bm, bn, bk) with provenance for reporting.

    ``epilogue_saved_bytes`` is the HBM traffic the plan's fused epilogue
    eliminates versus the unfused op graph (2*M*N per fused elementwise op —
    see transfer_model.PallasGemmTiling.epilogue_saved_bytes); 0 for a plain
    GEMM.  ``hbm_bytes`` is the fused kernel's own traffic, so roofline
    consumers credit the fusion as  unfused = hbm_bytes + epilogue_saved.
    """

    bm: int
    bn: int
    bk: int
    hbm_bytes: int
    vmem_bytes: int
    arithmetic_intensity: float
    grid_steps: int
    accumulate_in_vmem: bool = True
    epilogue_saved_bytes: int = 0

    def block_shapes(self) -> Tuple[Tuple[int, int], Tuple[int, int], Tuple[int, int]]:
        return (self.bm, self.bk), (self.bk, self.bn), (self.bm, self.bn)


def _round_up(x: int, mult: int) -> int:
    return mult * -(-x // mult)


def _candidate_dims(dim: int, align: int, cap: int) -> List[int]:
    """Aligned candidate block sizes covering a dimension of size `dim`."""
    cands = []
    b = align
    while b < min(dim, cap):
        cands.append(b)
        b *= 2
    cands.append(min(_round_up(dim, align), cap))
    return sorted(set(cands))


def plan_matmul_tiles(
    p: GemmProblem,
    *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    accumulate_in_vmem: bool = True,
    max_block: int = 4096,
    fused_epilogue_ops: int = 0,
    gated: bool = False,
) -> TilePlan:
    """Search (bm, bn, bk) minimizing HBM traffic under the VMEM budget.

    Mirrors the paper's search over tile/sub-tile configs in Table IV:
    the objective is the Table I ref. 1) total with inter-k buffering
    (MX) or without (baseline), and the constraint is the lower-level
    capacity (VMEM here, the 256 B buffer there).

    ``fused_epilogue_ops`` > 0 records how many elementwise ops ride the
    final-k write-back; the returned plan carries the resulting
    ``epilogue_saved_bytes`` credit.  The savings are tile-shape independent
    (2*M*N per op), so they don't perturb the search ordering — they change
    what the roofline reports, not which tiles win.  ``gated`` (SwiGLU)
    stages a second weight block and accumulator, which the VMEM budget
    counts.

    Tie-breaks (in order): fewer grid steps (higher "SIMD ratio" — the
    paper's instruction-amortization argument), larger bk (longer
    accumulation chains), squarer (bm, bn).
    """
    # Alignment follows the A operand's element size (the sublane dim of the
    # (bm, bk) block); a narrower B only changes the byte accounting below.
    sub = _SUBLANE[p.a_elem_bytes]
    bm_cands = _candidate_dims(p.M, max(sub, min(MXU_DIM, _round_up(p.M, sub))), max_block)
    bn_cands = _candidate_dims(p.N, min(MXU_DIM, _round_up(p.N, MXU_DIM)), max_block)
    bk_cands = _candidate_dims(p.K, min(MXU_DIM, _round_up(p.K, sub)), max_block)

    best: Optional[Tuple] = None
    best_plan: Optional[TilePlan] = None
    for bm in bm_cands:
        for bn in bn_cands:
            for bk in bk_cands:
                tiling = PallasGemmTiling(
                    bm, bn, bk, accumulate_in_vmem=accumulate_in_vmem,
                    fused_epilogue_ops=fused_epilogue_ops, gated=gated,
                )
                # What Mosaic allocates: double-buffered blocks (Pallas
                # pipelines the next block DMA while the MXU consumes the
                # current one), accumulators, dot results.  A 2:4-sparse B
                # stages compressed payload + metadata, so sparse weights
                # buy larger tiles under the same budget — the
                # narrow-operand argument again.
                vmem = tiling.vmem_bytes(p)
                if vmem > vmem_budget:
                    continue
                traffic = tiling.hbm_bytes(p)
                key = (
                    traffic,
                    tiling.grid_steps(p),
                    -bk,
                    abs(math.log(bm / bn)) if bn else 0.0,
                )
                if best is None or key < best:
                    best = key
                    best_plan = TilePlan(
                        bm=bm,
                        bn=bn,
                        bk=bk,
                        hbm_bytes=traffic,
                        vmem_bytes=vmem,
                        arithmetic_intensity=tiling.arithmetic_intensity(p),
                        grid_steps=tiling.grid_steps(p),
                        accumulate_in_vmem=accumulate_in_vmem,
                        epilogue_saved_bytes=tiling.epilogue_saved_bytes(p),
                    )
    if best_plan is None:
        raise ValueError(
            f"no feasible tile plan for {p} under vmem_budget={vmem_budget}"
        )
    return best_plan


def paper_subtile_space() -> Iterable[Tuple[int, int, int]]:
    """The paper's feasible sub-tile space: m', n', k' in {4, 8} under the
    256 B buffer (m'*n' output elements * 8 B <= 256 B for FP64)."""
    for m_ in (4, 8):
        for n_ in (4, 8):
            for k_ in (4, 8):
                if m_ * n_ * 8 <= 256:
                    yield (m_, n_, k_)
