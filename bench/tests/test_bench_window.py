"""End-to-end arithmetic over a window with a warm-up before it and a
drain after it."""
from types import SimpleNamespace

import numpy as np
import pytest

import run
from mxbench.loop import Record, Step, Window
from mxbench.stats import percentile
from mxbench.view import RunView


def _window():
    # window [10, 12] s; request 1 due at 10.0, tokens at 10.5, 11.0, 11.5,
    # 12.5 (the last after the window); request 2 due at 11.0, first token
    # only in the drain at 12.2
    recs = {1: Record(1, 9, 4, 10.0, first=10.5,
                      times=[10.5, 11.0, 11.5, 12.5]),
            2: Record(2, 5, 4, 11.0, first=12.2, times=[12.2])}
    steps = [Step(10.0, 10.5, 1, 0.25, 0.1), Step(10.6, 11.0, 2, 0.5, 0.2),
             Step(11.0, 11.5, 2, 0.5, 0.2), Step(11.6, 12.0, 2, 0.5, 0.3),
             Step(12.0, 12.2, 2, 0.75, 0.3)]
    return Window(10.0, 12.0, recs, steps)


def test_end_to_end_over_window_and_drain():
    m = run.end_to_end(_window())
    # TTFT counts the drain: 500 ms and 1200 ms
    assert m["ttft_p95_ms"] == pytest.approx(500 + 0.95 * 700)
    # gaps of tokens emitted in the window: 500, 500 (12.5 is outside)
    assert m["itl_p50_ms"] == pytest.approx(500)
    assert m["itl_p95_ms"] == pytest.approx(500)
    # three tokens in a two-second window
    assert m["output_tok_s"] == pytest.approx(1.5)


def test_slots_busy_mean_counts_window_steps_only():
    view = RunView({}, 1, {}, _window(), 0)
    assert run.metric_reader("slots_busy_mean").read(view) == \
        pytest.approx(7 / 4)


def test_kv_pool_reserved_counts_window_steps_only():
    view = RunView({}, 1, {}, _window(), 0)
    assert run.metric_reader("kv_pool_reserved_pct").read(view) == \
        pytest.approx(100 * 1.75 / 4)


def test_percentile_and_spread():
    assert percentile([], 95) is None
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)


def test_mfu_counts_the_window_work():
    c = {"hidden_size": 64, "num_hidden_layers": 2, "intermediate_size": 128,
         "vocab_size": 512, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16}
    from mxbench import work

    view = RunView(c, 1, {"flops_per_s": 1e9}, _window(), 0)
    want = (work.prompt_flops(c, 9) + work.token_flops(c, 9, True)
            + work.token_flops(c, 10, True))
    assert run.metric_reader("mfu").read(view) == \
        pytest.approx(100 * want / (2.0 * 1e9))


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class _Batcher:
    """Two slots; each step gives every live request one token and takes
    0.1 s of the fake clock."""

    def __init__(self, clock):
        self.clock, self.queue, self.live, self.health = clock, [], [], []

    @property
    def active(self):
        return len(self.live)

    def submit(self, req):
        self.queue.append(req)

    def pool_stats(self):
        return SimpleNamespace(utilization=len(self.live) / 2, live_tokens=0,
                               num_pages=2, page_size=4)

    def step(self):
        while self.queue and len(self.live) < 2:
            self.live.append(self.queue.pop(0))
        for r in self.live:
            r.output.append(1)
            if len(r.output) >= r.max_new:
                r.finish_reason = "max_new"
        self.health.append(SimpleNamespace(active=len(self.live)))
        self.live = [r for r in self.live if r.finish_reason is None]
        self.clock.t += 0.1


def _drive(monkeypatch, warm_s):
    from mxbench import loop
    from mxbench.traffic import Planned

    clock = _Clock()
    monkeypatch.setattr(loop.time, "sleep",
                        lambda s: setattr(clock, "t", clock.t + s))
    batcher = _Batcher(clock)
    plan = [Planned(rid=i, prompt=np.zeros(3, np.int32), max_new=3,
                    due_s=0.25 * i) for i in range(16)]
    driver = loop.Driver(batcher, lambda p: SimpleNamespace(
        rid=p.rid, output=[], max_new=p.max_new, finish_reason=None),
        clock=clock)
    opened = []
    win = driver.run(plan, 1.5, warm_s=warm_s, at_open=opened.append)
    return win, opened


def test_window_opens_after_the_warm_up(monkeypatch):
    win, opened = _drive(monkeypatch, warm_s=1.0)
    assert opened == [win.t0]
    assert 101.0 <= win.t0 < 101.1 and win.t1 >= win.t0 + 1.5
    due = win.due_in_window()
    assert due and all(win.t0 <= r.due <= win.t1 for r in due)
    # requests of the warm-up were served before the window opened
    assert any(r.finished_at is not None and r.finished_at < win.t0
               for r in win.records.values())
    assert all(s.start >= win.t0 for s in win.window_steps())
    assert all(r.finished_at >= win.t0 for r in win.finished())
    assert not win.unanswered


def test_without_warm_up_the_window_opens_at_once(monkeypatch):
    win, opened = _drive(monkeypatch, warm_s=0.0)
    assert win.t0 == 100.0 and opened == [100.0]
    assert min(r.due for r in win.due_in_window()) == 100.0


def test_load_reports_the_backlog():
    win = _window()
    win.queued = (0, 3)
    got = run.load(win)
    assert got["queued_at_open"] == 0 and got["queued_at_close"] == 3
    assert got["ttft_p50_ms_halves"] == [pytest.approx(500),
                                         pytest.approx(1200)]
    assert got["offered_tok_s"] == pytest.approx(4.0)
    assert got["slots_busy_mean"] == pytest.approx(7 / 4)
    assert got["kv_pool_live_pct"] == pytest.approx(100 * 0.8 / 4)
