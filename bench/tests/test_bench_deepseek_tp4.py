"""The four-chip cell `deepseek-67b-tp4.chat`: its files join, the program's
parameter tree matches the reference's layout at full width, the reckoned
bytes fit a chip, the collective readers on synthetic traces, and the two
run wrappers the cell needs (bench/control.py, bench/memory.py) on a tiny
cell."""
import math
from pathlib import Path

import jax
import numpy as np
import pytest

import run
from mxbench import collectives, devtrace
from mxbench.view import RunView

MS = 1_000_000  # ns
CELL = "deepseek-67b-tp4.chat"
F32 = 4


@pytest.fixture(scope="module")
def spec():
    return run.load_cell(CELL)


def test_the_cell_joins_its_config_mix_and_metrics(spec):
    assert spec["cell"]["chips"] == 4 and spec["cell"]["traffic"] == "chat-tp4"
    c = spec["cfg"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"]) == (8192, 64, 8, 128, 22016, 102400)
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"]["num_hidden_layers"] == 95
    assert not c["tie_word_embeddings"] and not c["attention_bias"]
    assert c["pool_pages"] == c["slots"] * -(-c["max_len"] // c["page_size"])
    assert spec["mix"]["loop"] == "open"
    names = {m["name"] for m in spec["per_layer"]}
    assert {"tp_collective_ms_per_step",
            "tp_collective_exposed_ms_per_step"} <= names
    # the readers exist and the mix's requests fit the slots' pages
    for name in names:
        assert callable(run.metric_reader(name).read)
    from mxbench import traffic
    assert traffic.max_tokens(spec["mix"]) <= c["max_len"]


def test_the_program_tree_is_the_reference_layout_at_full_width(spec):
    from repro.models import build_model

    from mxbench import weights as W

    cfg = spec["cfg"]
    model = build_model(run.arch_config(cfg))
    mine = W.abstract(run.reference_module(cfg).layout(cfg))
    theirs = model.abstract()
    assert jax.tree.structure(theirs) == jax.tree.structure(mine)
    for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(mine)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_the_reckoned_bytes_fit_one_chip(spec):
    """What a chip holds: between programs the weights' and the pool's
    shares; at the peak also what two programs in flight add, since nothing
    is donated: each one's new pool and its compiled temporaries (1.00 GB
    for the decode step, 1.15 GB for a 128-token prefill chunk, from
    bench/rehearse.py on a described v5e:2x2).  On a TPU v5e 2x2 host the
    chips read 8.826 GB in use after the weight draw and a peak of
    14.60 GB in the window."""
    c, chips = spec["cfg"], spec["cell"]["chips"]
    layout = run.reference_module(c).layout(c)
    weights = sum(math.prod(s) for s, _, _ in layout.values()) * F32
    # K and V, every layer, this chip's share of the KV heads, and the
    # pool's dump page
    pool = (2 * c["num_hidden_layers"] * (c["pool_pages"] + 1)
            * c["page_size"] * c["num_key_value_heads"] // chips
            * c["head_dim"] * F32)
    resting = weights / chips + pool
    peak = resting + 2 * pool + 1.00e9 + 1.15e9
    assert weights / chips == pytest.approx(7.21e9, rel=0.01)
    assert resting == pytest.approx(8.826e9, rel=1e-3)
    assert peak == pytest.approx(14.60e9, rel=0.03)
    assert peak < run.peaks_for("TPU v5 lite")["hbm_bytes"]


def _view(ops, mods):
    tr = devtrace.Trace({"/device:TPU:0": devtrace.Device(ops, mods)},
                        [("bench.traced", 0, 100 * MS)])
    return RunView({}, 4, {}, None, 0, tr)


def _read(view):
    return (run.metric_reader("tp_collective_ms_per_step").read(view),
            run.metric_reader("tp_collective_exposed_ms_per_step").read(view))


def test_exposed_is_collective_time_less_what_compute_overlaps():
    ops = [("%while.1 = (s32[])", 0, 20 * MS),     # holds the layer loop
           ("%mx_matmul_fused.2 = f32[8]", 1 * MS, 5 * MS),
           ("%collective-permute-start.3 = f32[4]", 5 * MS, 6 * MS),
           ("%mx_matmul_fused.2 = f32[8]", 6 * MS, 9 * MS),
           ("%collective-permute-done.3 = f32[4]", 9 * MS, 11 * MS),
           ("%fusion.4 = f32[8]", 11 * MS, 13 * MS),
           ("%all-gather.5 = f32[32]", 12 * MS, 14 * MS),   # half hidden
           # a second decode step whose collective compute hides whole
           ("%fusion.6 = f32[8]", 31 * MS, 35 * MS),
           ("%all-reduce.7 = f32[8]", 32 * MS, 34 * MS),
           # a prefill program's collective counts in neither
           ("%reduce-scatter.8 = f32[8]", 61 * MS, 69 * MS)]
    mods = [("jit_step_paged(1)", 0, 20 * MS),
            ("jit_step_paged(1)", 30 * MS, 36 * MS),
            ("jit_prefill_paged(2)", 60 * MS, 70 * MS)]
    total, exposed = _read(_view(ops, mods))
    # collective time: 1 + 2 + 2 in the first step, 2 in the second
    assert total == pytest.approx((5 + 2) / 2)
    # exposed: 1 + 2 + 1 (the all-gather's second half) and 0
    assert exposed == pytest.approx((4 + 0) / 2)


def test_fully_hidden_collectives_read_zero_exposed():
    ops = [("%fusion.1 = f32[8]", 0, 10 * MS),
           ("%collective-permute.2 = f32[4]", 2 * MS, 4 * MS),
           ("%all-to-all.3 = f32[4]", 6 * MS, 9 * MS)]
    total, exposed = _read(_view(ops, [("jit_step_paged(1)", 0, 10 * MS)]))
    assert total == pytest.approx(5.0)
    assert exposed == 0.0


def test_collective_and_compute_names():
    assert collectives.is_collective(
        "%collective-permute-done.12 = f32[4,8192]{1,0} collective-permute-"
        "done(...)")
    assert collectives.is_collective("all-reduce-start.1")
    assert not collectives.is_collective("%mx_matmul_fused.3 = f32[8]")
    assert not collectives.is_collective("%fusion.9 = f32[8] fusion("
                                         "all-gather.1)")
    assert not collectives.is_compute("%while.3 = (s32[], f32[8])")
    assert not collectives.is_compute("%all-gather.1 = f32[4]")
    assert collectives.is_compute("%mx_matmul_fused.3 = f32[8]")


def test_readers_without_a_decode_program_return_nothing():
    assert _read(_view([("%all-gather.1 = f32[4]", 0, MS)], [])) == \
        (None, None)
    view = RunView({}, 4, {}, None, 0, None)
    assert _read(view) == (None, None)


TINY = {
    "configs": [{"name": "tiny", "file": "bench/tests/tiny.json"}],
    "workloads": [{"name": "tiny.chat", "config": "tiny",
                   "traffic": "tiny-chat", "chips": 1}],
    "end_to_end": [{"name": "itl_p50_ms", "unit": "ms"}],
    "per_layer": [],
}
TESTS = Path(__file__).resolve().parent


def _tiny(module, seed, **kw):
    return module.main(["--workload", "tiny.chat", "--seed", str(seed),
                        "--seconds", "2", "--trace", "0"], on_chip=False,
                       benchmark=TINY, traffic_dir=str(TESTS), **kw)


@pytest.mark.parametrize("name", ["high", "bf16"])
def test_the_blocked_head_product_is_the_whole_one(name):
    import control

    dot = run.reference_module({"reference": "dense_decoder"}).DOTS[name]
    rng = np.random.default_rng(5)
    a = rng.standard_normal((3, 16)).astype(np.float32)
    b = rng.standard_normal((16, 50)).astype(np.float32)
    np.testing.assert_allclose(control.blocked(dot, 7)("pd,dv->pv", a, b),
                               dot("pd,dv->pv", a, b), rtol=1e-6)
    # every other contraction is left whole
    x = np.ones((2, 3, 16), np.float32)
    np.testing.assert_array_equal(control.blocked(dot, 7)(
        "spd,dv->spv", x, b), dot("spd,dv->spv", x, b))


def test_the_control_run_in_blocks_comes_out_not_correct():
    """bench/control.py judges the control, its head product in blocks of
    a few columns, in the program's place: not correct, while the program's
    own readings are within their limits."""
    import control

    res = _tiny(control, 2**31 + 17, columns=5)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_err"]["value"] > \
        res["checks"]["max_logit_err"]["limit"]
    assert all(c["value"] <= c["limit"] for n, c in
               res["program_checks"].items() if n != "kept_rows")
    assert run.reference_module.__module__ == "run"


def test_the_memory_run_logs_every_phase_in_order(capsys):
    import memory
    from mxbench import weights

    plain = weights.make
    res = _tiny(memory, 2**31 + 19)
    err = capsys.readouterr().err
    phases = [line.split(" {")[0][len("MEM "):]
              for line in err.splitlines() if line.startswith("MEM ")]
    assert phases == list(memory.PHASES)
    assert res["correct"], res["checks"]
    assert weights.make is plain and run.warm_up.__module__ == "run"
