"""Tile planner and energy model tests."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import paper_data
from repro.core.energy import (
    access_counters, fit_energy_model, modeled_gain,
)
from repro.core.tiling import DEFAULT_VMEM_BUDGET, plan_matmul_tiles
from repro.core.transfer_model import GemmProblem, PallasGemmTiling


dims = st.sampled_from([256, 512, 1024, 4096, 8192])


@settings(max_examples=25, deadline=None)
@given(M=dims, N=dims, K=dims, eb=st.sampled_from([2, 4]))
def test_plan_respects_vmem_budget(M, N, K, eb):
    p = GemmProblem(M, N, K, eb)
    plan = plan_matmul_tiles(p)
    assert plan.vmem_bytes <= DEFAULT_VMEM_BUDGET
    # MXU alignment on the lane dim
    assert plan.bn % 128 == 0 or plan.bn >= N
    assert plan.bm % 8 == 0


@settings(max_examples=20, deadline=None)
@given(M=dims, N=dims, K=dims)
def test_plan_beats_naive_128_tile(M, N, K):
    """The planner's traffic is never worse than the default 128^3 tiling
    (it searches a superset)."""
    p = GemmProblem(M, N, K, 2)
    plan = plan_matmul_tiles(p)
    naive = PallasGemmTiling(128, 128, 128).hbm_bytes(p)
    assert plan.hbm_bytes <= naive


def test_planner_prefers_inter_k_buffering():
    p = GemmProblem(4096, 4096, 4096, 2)
    mx = plan_matmul_tiles(p, accumulate_in_vmem=True)
    base = plan_matmul_tiles(p, accumulate_in_vmem=False)
    assert mx.hbm_bytes <= base.hbm_bytes


def test_paper_subtile_space_respects_buffer():
    """m'*n' FP64 output sub-tile must fit the 256 B MX buffer (paper §III)."""
    from repro.core.tiling import paper_subtile_space

    for m_, n_, k_ in paper_subtile_space():
        assert m_ * n_ * 8 <= 256
        assert m_ in (4, 8) and n_ in (4, 8) and k_ in (4, 8)


# --------------------------- energy model ---------------------------


def test_counters_monotone_in_problem_size():
    small = access_counters(paper_data.best_row("dual", "mx", 16))
    big = access_counters(paper_data.best_row("dual", "mx", 64))
    for k in ("mem", "vrf", "mac"):
        assert big[k] > small[k]


def test_energy_fit_reproduces_dual_core_gain():
    """Fit on the dual-core rows; the modeled MX-vs-baseline 64^3 efficiency
    gain must land near the paper's +10.9% headline."""
    rows = paper_data.rows("dual")
    model = fit_energy_model(rows, "dual")
    g = modeled_gain(model, "dual", 64)
    assert abs(g["modeled"] - g["paper"]) < 0.05, g
    assert g["paper"] == pytest.approx(0.109, abs=0.01)


def test_energy_fit_generalizes_leave_out():
    """Fit ONLY on the 16^3/32^3 rows, predict the held-out 64^3 gain."""
    train_rows = [r for r in paper_data.rows("dual") if r.size < 64]
    model = fit_energy_model(train_rows, "dual")
    g = modeled_gain(model, "dual", 64)
    # direction and rough magnitude must hold out of sample
    assert g["modeled"] > 0.0, f"predicted no MX gain: {g}"
    assert abs(g["modeled"] - g["paper"]) < 0.10, g


def test_energy_coefficients_physical():
    """Memory-hierarchy energy pyramid: TCDM access >= VRF access cost."""
    model = fit_energy_model(paper_data.rows("dual"), "dual")
    c = model.coef
    assert c["mem"] >= 0 and c["vrf"] >= 0 and c["mac"] >= 0
    if c["vrf"] > 0:
        assert c["mem"] + 1e-18 >= c["vrf"] * 0.5  # mem no cheaper than ~VRF


def test_vmem_count_matches_the_compiler():
    """The buffers `gemm_vmem_bytes` counts are what Mosaic allocates for a
    TPU v5e (its "Scoped allocation with size ..." for these launches):
    (512, 2048, 256) f32 is 17 MiB of double-buffered blocks plus the
    accumulator, 25 MiB when SwiGLU-gated; the f32 dot results come on top
    (4 MiB per accumulator)."""
    from repro.kernels.vmem import MiB, gemm_vmem_bytes

    kw = dict(a_bytes=4, b_bytes=4, out_bytes=4)
    assert gemm_vmem_bytes(512, 2048, 256, **kw) == 17 * MiB + 4 * MiB
    assert gemm_vmem_bytes(512, 2048, 256, gated=True, **kw) == 25 * MiB + 8 * MiB
    # an (8, K) bf16 block pads to whole (8, 128) tiles, not 16 rows
    assert gemm_vmem_bytes(8, 4096, 2048, a_bytes=2, b_bytes=2,
                           out_bytes=2) == 32 * MiB + 320 * 1024 + 128 * 1024
