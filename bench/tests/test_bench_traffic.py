"""The traffic generator: fixed work per seed, arranged by the seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from mxbench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mixes():
    return sorted(MIXES.glob("*.json"))


@pytest.mark.parametrize("path", _mixes(), ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = json.loads(path.read_text())
    a = traffic.plan(mix, 5.0, 2**31 + 7, 1000)
    b = traffic.plan(mix, 5.0, 2**31 + 7, 1000)
    assert [(p.rid, p.max_new, p.due_s) for p in a] == \
        [(p.rid, p.max_new, p.due_s) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


@pytest.mark.parametrize("path", _mixes(), ids=lambda p: p.stem)
def test_seeds_share_the_work_not_its_order(path):
    mix = json.loads(path.read_text())
    a = traffic.plan(mix, 5.0, 1, 1000)
    b = traffic.plan(mix, 5.0, 2, 1000)
    assert sorted((len(p.prompt), p.max_new) for p in a) == \
        sorted((len(p.prompt), p.max_new) for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert a[-1].due_s == pytest.approx(b[-1].due_s)


@pytest.mark.parametrize("path", _mixes(), ids=lambda p: p.stem)
def test_seeds_move_requests_only_within_a_block(path):
    mix = json.loads(path.read_text())
    k = mix["order_block"]
    a, b = (traffic.plan(mix, 50.0, s, 1000) for s in (5, 2**31 + 3))
    for lo in range(0, len(a), k):
        def block(plan):
            return sorted((len(p.prompt), p.max_new)
                          for p in plan[lo:lo + k])
        assert block(a) == block(b)
    # every seed gets the same arrival times
    assert [p.due_s for p in a] == [p.due_s for p in b]


def test_spread_order_puts_long_requests_all_through_the_run():
    mix = {"loop": "open", "rate_per_s": 1.0, "order_block": 4,
           "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                      "min": 32, "max": 2048},
           "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                      "min": 16, "max": 1024}}
    plan = traffic.plan(mix, 120.0, 9, 50)
    longest = sorted(plan, key=lambda p: -len(p.prompt))[:12]
    thirds = np.bincount([3 * p.rid // len(plan) for p in longest],
                         minlength=3)
    assert thirds.min() >= 3


@pytest.mark.parametrize("path", _mixes(), ids=lambda p: p.stem)
def test_page_length_rule(path):
    mix = json.loads(path.read_text())
    page = mix["prompt"].get("page_multiple")
    lens = traffic.lengths(mix["prompt"], 500)
    assert lens.min() >= mix["prompt"]["min"]
    assert lens.max() <= mix["prompt"]["max"]
    if page:
        assert np.all((lens - 1) % page == 0)


def test_open_loop_rate_and_ids():
    mix = {"loop": "open", "rate_per_s": 20.0, "order_block": 4,
           "prompt": {"dist": "lognormal", "median": 20, "sigma": 1.0,
                      "min": 5, "max": 100, "page_multiple": 4},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 40}}
    plan = traffic.plan(mix, 10.0, 3, 50)
    assert len(plan) == 200
    dues = [p.due_s for p in plan]
    assert dues == sorted(dues)
    assert 8.0 < dues[-1] < 12.0
    assert all(p.prompt.dtype == np.int32 and p.prompt.max() < 50
               for p in plan)


def test_warm_up_adds_requests_before_the_window():
    mix = {"loop": "open", "rate_per_s": 20.0, "warm_s": 5.0,
           "order_block": 4,
           "prompt": {"dist": "lognormal", "median": 20, "sigma": 1.0,
                      "min": 5, "max": 100},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 40}}
    plan = traffic.plan(mix, 10.0, 3, 50)
    assert len(plan) == 300
    assert 13.0 < plan[-1].due_s < 17.0
    assert sum(p.due_s < 5.0 for p in plan) > 80


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf"}, 3)
