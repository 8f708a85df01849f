"""The comparison that decides `correct`, driven through a whole run of the
harness on the CPU at a test's size (the look for a chip skipped): a sound
run passes, the precision control fails, and each fault this serving cell
can have, planted in the timed path, turns `correct` false."""
import json
from pathlib import Path

import pytest

import run

TESTS = Path(__file__).resolve().parent
BENCH = {
    "configs": [{"name": "tiny", "file": "bench/tests/tiny.json"}],
    "workloads": [{"name": "tiny.chat", "config": "tiny",
                   "traffic": "tiny-chat", "chips": 1}],
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("itl_p50_ms", "ms"), ("itl_p95_ms", "ms"),
        ("output_tok_s", "tokens/s"), ("setup_s", "s"))],
    "per_layer": [{"name": "slots_busy_mean", "unit": "slots"}],
}
LIMITS = json.loads((TESTS / "tiny.json").read_text())["checks"]


def _run(seed, **kw):
    return run.main(["--workload", "tiny.chat", "--seed", str(seed),
                     "--seconds", "2", "--trace", "0"], on_chip=False,
                    benchmark=BENCH, traffic_dir=str(TESTS), **kw)


def test_sound_run_is_correct_and_the_control_is_not():
    """One run judges the reference at `high` in the program's place: the
    run's `correct` is the control's and comes out false, while the
    program's own readings, beside it, are within every limit."""
    res = _run(2**31 + 11, control=True)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_err"]["value"] > LIMITS["max_logit_err"]
    prog = res["program_checks"]
    assert all(c["value"] <= c["limit"] for n, c in prog.items()
               if n != "kept_rows"), prog
    assert prog["kept_rows"]["value"] >= 8
    assert res["failed"] == 0 and res["attempted"] > 10
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    # an altered token reads far above the token-gap limit
    assert res["token_fault"]["max_token_gap"] > 100 * LIMITS["max_token_gap"]


def test_sound_run_is_correct():
    res = _run(2**31 + 13)
    assert res["correct"], res["checks"]
    assert "program_checks" not in res and list(res)[-1] == "checks"


@pytest.mark.parametrize("field,value,unanswered,want", [
    ("max_logit_err", 0.0, 0, True),
    ("max_logit_err", 2 * LIMITS["max_logit_err"], 0, False),
    ("max_token_gap", 2 * LIMITS["max_token_gap"], 0, False),
    ("rows", 0, 0, False),
    ("max_logit_err", 0.0, 1, False)])
def test_verdict_holds_each_number_to_its_limit(field, value, unanswered,
                                                want):
    from mxbench.check import Readings
    from mxbench.loop import Window

    r = Readings(max_logit_err=0.0, max_token_gap=0.0, rows=4, tokens=9)
    setattr(r, field, value)
    win = Window(0.0, 1.0, {}, [], unanswered=list(range(unanswered)))
    correct, checks = run.verdict(r, LIMITS, win)
    assert correct is want
    assert set(checks) == {"max_logit_err", "max_token_gap", "kept_rows",
                           "unanswered"}


def _cache_unchanged(batcher):
    inner = batcher._step

    def step(params, token, cache, *rest):
        logits, _ = inner(params, token, cache, *rest)
        return logits, cache

    batcher._step = step


def _token_altered(batcher):
    inner = batcher.step
    vocab = batcher.model.cfg.vocab

    def step():
        before = {s.req.rid: len(s.req.output) for s in batcher.slots
                  if s.req is not None}
        n = inner()
        for req in list(batcher.finished.values()) + [
                s.req for s in batcher.slots if s.req is not None]:
            if len(req.output) > before.get(req.rid, len(req.output)):
                req.output[-1] = (req.output[-1] + 1) % vocab
        return n

    batcher.step = step


@pytest.mark.parametrize("fault", [_cache_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_faults_turn_correct_false(fault):
    res = _run(2**31 + 12, fault=fault)
    assert not res["correct"], res["checks"]
