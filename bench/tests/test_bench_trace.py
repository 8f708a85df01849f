"""Trace reduction on a small synthetic trace: busy union, idle share,
kernel sums inside programs, gap naming, and the readers built on them."""
import pytest

import run
from mxbench import devtrace, work
from mxbench.loop import Record, Step, Window
from mxbench.view import RunView

MS = 1_000_000  # ns


def _trace():
    # one device; traced window [0, 100 ms]; two decode programs and one
    # prefill program, with kernels inside them and one op outside
    ops = [("mx_matmul_fused.1", 10 * MS, 14 * MS),
           ("mx_flash_decode.2", 14 * MS, 16 * MS),
           ("mx_matmul_fused.1", 16 * MS, 20 * MS),
           ("fusion.7", 18 * MS, 22 * MS),          # overlaps the GEMM
           ("mx_matmul_fused.1", 40 * MS, 44 * MS),
           ("mx_flash_decode.2", 44 * MS, 46 * MS),
           ("mx_matmul_fused.1", 46 * MS, 50 * MS),
           ("mx_matmul_fused.3", 70 * MS, 80 * MS),  # in prefill
           ("copy.1", 95 * MS, 105 * MS)]            # runs past the window
    mods = [("jit_step_paged(1)", 10 * MS, 22 * MS),
            ("jit_step_paged(1)", 40 * MS, 50 * MS),
            ("jit_prefill_paged(2)", 70 * MS, 80 * MS)]
    spans = [("bench.traced", 0, 100 * MS),
             ("bench.step", 5 * MS, 30 * MS),
             ("bench.step", 35 * MS, 90 * MS),
             ("bench.wait", 90 * MS, 100 * MS)]
    return devtrace.Trace({"/device:TPU:0": devtrace.Device(ops, mods)},
                          spans)


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (28, 29)]
    assert devtrace.union_ns(iv, 0, 100) == 25
    assert devtrace.union_ns(iv, 8, 25) == 12
    assert devtrace.gaps(iv, 0, 40) == [(15, 20), (30, 40)]


def test_busy_and_idle_share():
    tr = _trace()
    busy = devtrace.busy(tr)["/device:TPU:0"]
    # 10-22, 40-50, 70-80, 95-100 (clipped): 12 + 10 + 10 + 5 ms
    assert busy == pytest.approx(0.037)
    view = RunView({}, 1, {}, None, 0, tr)
    share = run.metric_reader("device_idle_share").read(view)
    assert share == pytest.approx(63.0)


def test_idle_gaps_named_by_host_span():
    gaps = devtrace.idle_gaps(_trace(), "/device:TPU:0", top=3)
    assert gaps == [("bench.step", pytest.approx(0.020)),   # 50-70 ms
                    ("outside", pytest.approx(0.018)),      # 22-40 ms
                    ("bench.step", pytest.approx(0.015))]   # 80-95 ms


def test_kernels_inside_programs():
    tr = _trace()
    view = RunView({}, 1, {}, None, 0, tr)
    dev = tr.devices["/device:TPU:0"]
    assert len(view.program_runs(dev, "step_paged")) == 2
    gemm = view.kernel_in_program(dev, "mx_matmul_fused", "step_paged")
    assert devtrace.total_s(gemm) == pytest.approx(0.016)
    ops = dict(devtrace.top_ops(tr))
    assert ops["mx_matmul_fused"] == pytest.approx(0.026)
    assert devtrace.base_name("jit_step_paged(12)") == "jit_step_paged"
    assert devtrace.base_name(
        "%mx_matmul_fused.46 = f32[128,128]{1,0:T(8,128)S(1)} custom-call("
        "f32[128,896]{1,0} %multiply_bitcast_fusion.3)") == "mx_matmul_fused"
    assert devtrace.base_name("%while.3 = (s32[], f32[1,128,896])") == "while"
    assert run.metric_reader("decode_step_ms").read(view) == pytest.approx(11.0)


def _cfg():
    return {"hidden_size": 64, "num_hidden_layers": 2, "intermediate_size": 128,
            "vocab_size": 512, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "attention_bias": True,
            "slots": 4}


def test_roofline_readers_against_hand_counts():
    tr = _trace()
    c = _cfg()
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # two traced steps; step 1 emits token 0 of request 1 (prompt 9), step 2
    # tokens 1 and 0 of requests 1 and 2 (prompt 5)
    recs = {1: Record(1, 9, 4, 0.0, first=0.02, times=[0.02, 0.05]),
            2: Record(2, 5, 4, 0.0, first=0.05, times=[0.05])}
    win = Window(0.0, 0.1, recs, [Step(0.0, 0.02, 1), Step(0.03, 0.05, 2)])
    view = RunView(c, 1, peak, win, 0, tr, (0, 2))
    per_step = sum(work.roofline_s(f, b, peak)
                   for f, b in work.decode_gemms(c, 4))
    got = run.metric_reader("gemm_roofline_decode").read(view)
    assert got == pytest.approx(100 * 2 * per_step / 0.016)
    need = sum(work.roofline_s(*work.flash_decode(c, live, n), peak)
               for live, n in ((9, 1), (10 + 5, 2)))
    got = run.metric_reader("flash_decode_roofline").read(view)
    assert got == pytest.approx(100 * need / 0.004)
    # prefill: requests whose first token came in a traced step, 8 + 4
    # prompt tokens, 10 ms of prefill program
    got = run.metric_reader("prefill_ms_per_ktok").read(view)
    assert got == pytest.approx(10.0 / 12 * 1000)
    # the whole decode step: three tokens (positions 8, 9 and 4) over the
    # two decode programs' 22 ms
    flops = sum(work.token_flops(c, p, True) for p in (8, 9, 4))
    got = run.metric_reader("decode_step_mfu").read(view)
    assert got == pytest.approx(100 * flops / (0.022 * 1e12))


def test_readers_without_a_trace_return_nothing():
    win = Window(0.0, 1.0, {}, [])
    view = RunView(_cfg(), 1, {}, win, 0, None)
    for name in ("decode_step_ms", "prefill_ms_per_ktok",
                 "gemm_roofline_decode", "flash_decode_roofline",
                 "device_idle_share", "mfu", "decode_step_mfu",
                 "slots_busy_mean",
                 "kv_pool_reserved_pct"):
        assert run.metric_reader(name).read(view) is None, name


def test_gemm_work_counts():
    f, b = work.gemm(2, 3, 5, bias=True, residual=True)
    assert f == 2 * 2 * 3 * 5
    assert b == 4 * (3 * 5 + 2 * 3 + 2 * 5 + 5 + 2 * 5)
    c = _cfg()
    assert len(work.decode_gemms(c, 4)) == 6 * 2 + 1
    assert work.layer_params(c) == 64 * 8 * 16 + 64 * 64 + 3 * 64 * 128


def test_top_ops_count_self_time_of_nested_ops():
    # a loop op (0-10 ms) holding two kernels; its own time is 4 ms
    ops = [("%while.3 = (s32[])", 0, 10 * MS),
           ("%mx_matmul_fused.1 = f32[8]", 1 * MS, 4 * MS),
           ("%copy.2 = f32[8]", 6 * MS, 9 * MS),
           ("%fusion.5 = f32[8]", 12 * MS, 13 * MS)]
    tr = devtrace.Trace({"/device:TPU:0": devtrace.Device(ops, [])},
                        [("bench.traced", 0, 20 * MS)])
    got = dict(devtrace.top_ops(tr))
    assert got == pytest.approx({"while": 0.004, "mx_matmul_fused": 0.003,
                                 "copy": 0.003, "fusion": 0.001})
