"""The batcher's step and request records: each step's phase durations and
counts (`StepHealth`), the wall stamps of every lifecycle event
(`Request.event_times`), the profiler spans of the same phases, and the
program names that trace readers key on."""
import glob
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import step_report
from repro.models import build_model
from repro.runtime.batcher import (
    DECODE_PROGRAM, PREFILL_PROGRAM, ContinuousBatcher, Request,
)

PHASES = ("admit_s", "prepare_s", "dispatch_s", "wait_s", "emit_s")


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("llama3.2-1b-smoke")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _serve(cfg, model, params, n=5, steps=None, **kw):
    """n requests of 3-13 prompt tokens through two slots; all submitted
    up front, so later ones wait in the queue."""
    b = ContinuousBatcher(model, params, batch_slots=2, max_len=32, **kw)
    rng = np.random.default_rng(3)
    for i in range(n):
        b.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab, 3 + 5 * (i % 3)).astype(np.int32), max_new=3))
    if steps is None:
        b.run_to_completion()
    else:
        for _ in range(steps):
            b.step()
    return b


@pytest.mark.parametrize("kw", [
    dict(paged=True, page_size=4, prefill_chunk=4),
    dict(paged=True, page_size=4, prefill_chunk=4, speculate=2),
    dict(),
], ids=["paged-chunked", "speculative", "dense"])
def test_step_records_time_each_phase_and_count_the_work(model_and_params,
                                                          kw):
    cfg, model, params = model_and_params
    b = _serve(cfg, model, params, **kw)
    health = list(b.health)
    assert len(health) == b.steps_run and len(b.finished) == 5
    for h in health:
        parts = [getattr(h, p) for p in PHASES]
        assert all(p >= 0 for p in parts), h
        assert sum(parts) <= h.dt_s, h
        if h.active:
            assert all(p > 0 for p in parts), h
            assert h.width > 0 if b.paged else h.width == 0
        else:
            assert parts[1:] == [0.0] * 4 and h.emitted == 0, h
    starts = [h.t_start for h in health]
    assert starts == sorted(starts)
    # per-step counts are the deltas of the batcher's totals
    assert sum(h.prefill_chunks for h in health) == b.prefill_launches
    assert sum(h.prefill_tokens for h in health) == b.prefill_tokens
    assert sum(h.admitted for h in health) == 5
    assert sum(h.emitted for h in health) == sum(
        len(r.output) for r in b.finished.values())
    if kw.get("prefill_chunk"):
        assert b.prefill_launches > 0
        assert b.prefill_tokens >= b.prefill_launches
    else:
        assert b.prefill_launches == b.prefill_tokens == 0


@pytest.mark.parametrize("speculate", [0, 2], ids=["decode", "speculative"])
def test_event_stamps_align_with_events(model_and_params, speculate):
    cfg, model, params = model_and_params
    b = _serve(cfg, model, params, paged=True, page_size=4, prefill_chunk=4,
               speculate=speculate)
    for r in b.finished.values():
        kinds = [k for k, _ in r.events]
        assert len(r.event_times) == len(r.events)
        assert r.event_times == sorted(r.event_times)
        assert kinds[0] == "submitted" and kinds[-1].startswith("finished:")
        assert kinds.index("first_token") > kinds.index("admitted")
        t = dict(zip(kinds, r.event_times))
        assert t["first_token"] >= t["admitted"] >= t["submitted"]
    # requests queued behind the two slots waited for them
    waits = [r.event_times[[k for k, _ in r.events].index("admitted")]
             - r.event_times[0] for r in b.finished.values()]
    assert max(waits) > 0


def test_operator_report_reads_the_records(model_and_params):
    cfg, model, params = model_and_params
    b = _serve(cfg, model, params, paged=True, page_size=4, prefill_chunk=4)
    lines = step_report(b, b.finished)
    assert lines[0].startswith("  step phases, mean ms over")
    assert "queue wait: p50" in lines[1] and "over 5 admitted" in lines[1]
    assert f"({b.prefill_tokens} in {b.prefill_launches} launches)" \
        in lines[2]
    steps = [h for h in b.health if h.active]
    tokens = sum(len(r.output) for r in b.finished.values())
    width = np.mean([h.width for h in steps])
    pages = np.mean([h.attn_pages for h in steps])
    assert lines[3] == (
        f"  per step: {5 / len(b.health):.2f} admitted, "
        f"{b.prefill_launches / len(b.health):.2f} prefill chunks; "
        f"per device step: {tokens / len(steps):.2f} tokens emitted, "
        f"table width {width:.1f} pages, attention reads {pages:.1f} pages "
        f"({pages / (b.B * width):.3f} of slots x width)")


@pytest.mark.parametrize("kw", [
    dict(paged=True, page_size=4, prefill_chunk=4),
    dict(paged=True, page_size=4, prefill_chunk=4, speculate=2),
], ids=["paged-chunked", "speculative"])
def test_attn_pages_counts_the_live_pages_of_each_step(model_and_params, kw):
    """`StepHealth.attn_pages` is the sum of ceil(length / page_size) over
    the lengths the step's program was given, and never more than the grid
    of slots x table width."""
    cfg, model, params = model_and_params
    b = ContinuousBatcher(model, params, batch_slots=2, max_len=32, **kw)
    given = []
    run = b._device_step

    def spy(args, fail_first, fn=None):
        given.append(np.asarray(args[-1]))
        return run(args, fail_first, fn=fn)

    b._device_step = spy
    rng = np.random.default_rng(3)
    for i in range(5):
        b.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab, 3 + 5 * (i % 3)).astype(np.int32), max_new=3))
    b.run_to_completion()
    steps = [h for h in b.health if h.active]
    assert len(steps) == len(given) > 0
    for h, lengths in zip(steps, given):
        assert h.attn_pages == sum(-(-int(n) // 4) for n in lengths), h
        assert 0 < h.attn_pages <= b.B * h.width


def test_phases_are_profiler_spans(model_and_params, tmp_path):
    from jax.profiler import ProfileData

    cfg, model, params = model_and_params
    b = ContinuousBatcher(model, params, batch_slots=3, max_len=32,
                          paged=True, page_size=4, prefill_chunk=4)
    prompts = np.random.default_rng(3).integers(0, cfg.vocab, (3, 13))
    for rid, n in ((0, 3), (1, 8)):
        b.submit(Request(rid=rid, prompt=prompts[rid, :n], max_new=8))
    b.step()  # compiles outside the trace
    b.submit(Request(rid=2, prompt=prompts[2], max_new=8))
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            b.step()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("batcher."):
                        spans.setdefault(e.name, []).append(dict(e.stats))
    assert [s["step"] for s in spans["batcher.step"]] == [1, 2, 3]
    for name in ("admit", "prepare", "dispatch", "wait", "emit"):
        assert len(spans[f"batcher.{name}"]) == 3, name
    # the 13-token prompt admitted in step 1 prefills 12 tokens in 3 chunks
    assert spans["batcher.prefill"] == [{"rid": 2, "tokens": 4}] * 3


@pytest.mark.parametrize("speculate", [0, 2], ids=["decode", "speculative"])
def test_each_step_span_holds_one_of_each_device_phase(model_and_params,
                                                       tmp_path, speculate):
    """The decode step and the speculative window run the same phases: one
    ``batcher.step`` span holds exactly one span of each."""
    from jax.profiler import ProfileData

    cfg, model, params = model_and_params
    b = ContinuousBatcher(model, params, batch_slots=2, max_len=32,
                          paged=True, page_size=4, prefill_chunk=4,
                          speculate=speculate)
    prompts = np.random.default_rng(3).integers(0, cfg.vocab, (2, 8))
    for rid, n in ((0, 3), (1, 8)):
        b.submit(Request(rid=rid, prompt=prompts[rid, :n], max_new=16))
    b.step()  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            b.step()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("batcher.")]
    steps = [e for e in events if e.name == "batcher.step"]
    assert len(steps) == 3
    for step in steps:
        inside = [e.name for e in events if e is not step
                  and step.start_ns <= e.start_ns
                  and e.end_ns <= step.end_ns]
        assert sorted(inside) == sorted(
            f"batcher.{p}"
            for p in ("admit", "prepare", "dispatch", "wait", "emit")), inside


def test_program_names_are_what_trace_readers_key_on(model_and_params,
                                                     monkeypatch):
    """The decode and prefill XLA modules hold the names the benchmark's
    trace readers look for: a rename fails here, not as a silent null."""
    monkeypatch.syspath_prepend(str(Path(__file__).parents[1] / "bench"))
    from mxbench import view

    cfg, model, params = model_and_params
    b = ContinuousBatcher(model, params, batch_slots=2, max_len=32,
                          paged=True, page_size=4, prefill_chunk=4)
    table = jnp.asarray(b.pool.page_table(b.B, b._table_width))
    decode = b._step.lower(
        params, jnp.zeros((b.B, 1), jnp.int32), b.cache,
        jnp.zeros((b.B,), jnp.int32), table, jnp.asarray(b.pool.lengths(b.B)))
    prefill = b._prefill.lower(
        params, jnp.zeros((1, 4), jnp.int32), b.cache,
        jnp.zeros((1,), jnp.int32), table[:1])
    assert (DECODE_PROGRAM, PREFILL_PROGRAM) == (view.DECODE_PROGRAM,
                                                 view.PREFILL_PROGRAM)
    assert f"@jit_{view.DECODE_PROGRAM} " in decode.as_text()
    assert f"@jit_{view.PREFILL_PROGRAM} " in prefill.as_text()
