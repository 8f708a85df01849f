"""End-to-end system behaviour: the launchers run, losses move, serving
generates, the dry-run machinery lowers a smoke cell, HLO collective parsing
works on real lowered modules."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # multi-second subprocess launchers

REPO = Path(__file__).resolve().parents[1]


def _run(mod, *args, timeout=900):
    import os

    return subprocess.run(
        [sys.executable, "-m", mod, *args],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO,
    )


def test_train_launcher_end_to_end(tmp_path):
    r = _run("repro.launch.train", "--arch", "llama3.2-1b", "--smoke",
             "--steps", "8", "--batch", "2", "--seq", "16",
             "--ckpt-dir", str(tmp_path / "ck"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: 8 steps" in r.stdout


def test_train_launcher_failure_recovery(tmp_path):
    r = _run("repro.launch.train", "--arch", "llama3.2-1b", "--smoke",
             "--steps", "10", "--batch", "2", "--seq", "16",
             "--ckpt-every", "4", "--inject-failure", "6",
             "--ckpt-dir", str(tmp_path / "ck"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "1 restarts" in r.stdout


def test_serve_launcher(tmp_path):
    r = _run("repro.launch.serve", "--arch", "llama3.2-1b", "--smoke",
             "--batch", "2", "--prompt-len", "4", "--gen", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "generated (2, 4)" in r.stdout


@pytest.mark.slow
def test_serve_launcher_paged(tmp_path):
    r = _run("repro.launch.serve", "--arch", "llama3.2-1b", "--smoke",
             "--batch", "2", "--prompt-len", "4", "--gen", "3",
             "--paged", "--page-size", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "paged cache" in r.stdout
    assert "pages:" in r.stdout  # pages-in-use report


@pytest.mark.slow
def test_serve_launcher_chunked_prefill(tmp_path):
    r = _run("repro.launch.serve", "--arch", "llama3.2-1b", "--smoke",
             "--batch", "2", "--prompt-len", "8", "--gen", "3",
             "--prefill-chunk", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "prefill: 8 tokens in chunks of 4" in r.stdout
    assert "generated (2, 3)" in r.stdout


def test_collective_parser_on_canned_hlo():
    from repro.core.roofline import parse_collective_bytes

    hlo = """
HloModule test
ENTRY main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p0), channel_id=1
  %ag = f32[128,512]{1,0} all-gather(%p0), channel_id=2, dimensions={1}
  %cp = f32[128,256]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
  ROOT %r = f32[] constant(0)
}
"""
    stats = parse_collective_bytes(hlo)
    assert stats.count_by_kind["all-reduce"] == 1
    assert stats.bytes_by_kind["all-reduce"] == 128 * 256 * 4
    assert stats.bytes_by_kind["all-gather"] == 128 * 256 * 4  # operand bytes
    assert stats.bytes_by_kind["collective-permute"] == 128 * 256 * 4
    assert stats.total_count == 3


def test_roofline_report_math():
    from repro.core.roofline import RooflineReport

    r = RooflineReport(hlo_flops=197e12 * 256, hlo_bytes=819e9 * 256 * 2,
                       collective_bytes=0.0, chips=256, model_flops=197e12 * 128)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.bound == "memory"
    assert r.useful_flops_ratio == pytest.approx(0.5 / 256 * 256 / 2 * 2)  # 0.5
    assert r.roofline_fraction == pytest.approx(0.25)  # 0.5 useful / 2s bound


def test_dryrun_smoke_cell_subprocess():
    """One REAL production cell of the smallest arch via the actual CLI (the
    full 80-cell sweep runs out-of-band; this keeps CI time bounded)."""
    r = _run("repro.launch.dryrun", "--arch", "xlstm-125m",
             "--shape", "decode_32k", "--mesh", "single",
             "--out", "/tmp/dryrun_test", timeout=1200)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    rec = json.loads(Path("/tmp/dryrun_test/xlstm-125m__decode_32k__single.json").read_text())
    assert rec["status"] == "ok"
    assert rec["chips"] == 256
    assert rec["roofline"]["bound"] in ("compute", "memory", "collective")


def test_input_specs_cover_all_cells():
    """Every applicable (arch, shape) cell builds abstract specs without
    touching devices: 40 cells - 8 principled long_500k skips = 32 live."""
    from repro.configs import ARCH_IDS, SHAPES, cell_applicable, get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import cell_specs
    from repro.optim.adamw import AdamW
    from repro.parallel.sharding import make_rules

    mesh = make_mesh((1, 1), ("data", "model"))
    opt = AdamW()
    n_live = 0
    for a in ARCH_IDS:
        cfg = get_config(a)
        rules = make_rules(mesh, profile=cfg.parallelism, fsdp=cfg.fsdp)
        for s in SHAPES.values():
            ok, _ = cell_applicable(cfg, s)
            if not ok:
                continue
            specs = cell_specs(cfg, s, rules, opt=opt)
            assert specs.args and specs.in_shardings
            n_live += 1
    assert n_live == 32


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at the fixed <repo>/.jax_cache."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
