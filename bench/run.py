"""Runs one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of BENCHMARK.json joins a configuration (bench/configs/<name>.json,
with its plain reference in bench/reference/) and a traffic mix
(bench/traffic/<name>.json).  The run builds the serving program as its
launcher does (a paged continuous batcher with chunked prefill, on a
(1, chips) mesh, the ring collective policy when the mesh has several
chips), draws the weights on the device from the seed, warms every program
shape the mix can reach, then drives the batcher's own `submit`/`step` loop
for ``--seconds`` and stamps each request's times with the host clock.
After the window (and a drain that gives every request due in it its first
token) it frees the program's state and checks a sample of the finished
requests against the plain reference.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics with `--trace 1`), `device` and, last,
`checks`, each compared number beside its limit.  Without a TPU, with
fewer chips than the cell asks for, or without the program beside the
benchmark, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TRACE_START = 0.4   # the traced stretch starts this far into the window
TRACE_SECONDS = 6.0


class Refused(Exception):
    """The run cannot be made here; exit nonzero, print no result."""


def _load(path: Path):
    if not path.is_file():
        raise Refused(f"missing {path}")
    return json.loads(path.read_text())


def load_cell(name: str, bench=None, traffic_dir=None) -> dict:
    """The cell's entry joined with its configuration, mix and metrics
    (``bench`` and ``traffic_dir`` stand in for BENCHMARK.json and
    bench/traffic in the tests)."""
    bench = bench or _load(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load(ROOT / conf["file"])
    mix = _load(Path(traffic_dir or BENCH / "traffic")
                / f"{cell['traffic']}.json")

    def applies(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "cfg": cfg, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(
        ".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cfg: dict):
    return _module(BENCH / "reference" / f"{cfg['reference']}.py")


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py")


def peaks_for(kind: str) -> dict:
    table = _load(BENCH / "peaks.json")
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileCounter:
    """Programs built, counted by jax.monitoring: `backend_compile_duration`
    fires once per program, compiled or loaded from the persistent cache
    (`cache_hits` counts the loads).  Only that event is timed: the nested
    trace and lowering events would count the time twice."""

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def arch_config(cfg: dict):
    """The program's ArchConfig for the configuration file: the registered
    architecture with the file's sizes."""
    import dataclasses

    from repro.configs import get_config

    base = get_config(cfg["arch"])
    L = cfg["num_hidden_layers"]
    return dataclasses.replace(
        base, vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=L, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], blocks=(("dense", L),),
        qkv_bias=cfg["attention_bias"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def warm_shapes(cfg: dict, mix: dict, seconds: float):
    """(decode table widths, prefill chunk lengths) the mix can reach: the
    batcher buckets the decode table to a power of two of the deepest slot's
    pages, capped at max_len's, and prefills each prompt but its last token
    in chunks whose last one is the remainder."""
    from mxbench import traffic

    ps, chunk = cfg["page_size"], cfg["prefill_chunk"]
    full = -(-cfg["max_len"] // ps)
    plens = traffic.prompt_lengths(mix, seconds)
    deepest_lo, deepest_hi = int(plens.min()), traffic.max_tokens(mix) - 1
    widths = sorted({min(next_pow2(-(-d // ps)), full)
                     for d in range(deepest_lo, deepest_hi + 1)})
    tails = set()
    for p in plens:
        n = int(p) - 1
        if n >= 1:
            tails.add((n - 1) % chunk + 1)
            if n > chunk:
                tails.add(chunk)
    return widths, sorted(tails)


def warm_up(batcher, widths, tails):
    """Every decode width and prefill tail through the batcher's own
    compiled calls (writes land on the dump page and are dead by the
    length mask), plus the eager argmax that ends each step and the check's
    row slice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxbench import check

    B, pool = batcher.B, batcher.pool
    for w in widths:
        logits, batcher.cache = batcher._step(
            batcher.params, jnp.asarray(np.zeros((B, 1), np.int32)),
            batcher.cache, jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(pool.page_table(B, w)), jnp.asarray(pool.lengths(B)))
        np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        check.warm_row(logits)
    table = jnp.asarray(pool.page_table(B, batcher._table_width)[0:1])
    for c in tails:
        _, batcher.cache = batcher._prefill(
            batcher.params, jnp.asarray(np.zeros((1, c), np.int32)),
            batcher.cache, jnp.asarray([0], np.int32), table)
    jax.block_until_ready(batcher.cache)


class Tracer:
    """Starts the profiler at a step boundary TRACE_START into the window
    and stops it at the first boundary TRACE_SECONDS later."""

    def __init__(self, driver, seconds: float, log_dir: str):
        self.driver = driver
        self.seconds = seconds
        self.dir = log_dir
        self.t0 = self.t_on = None      # t0: the window's opening
        self.span = None
        self.first = self.last = None

    def __call__(self, n: int):
        import jax

        now = self.driver.clock()
        if self.t0 is None:
            return
        if self.first is None and now >= self.t0 + TRACE_START * self.seconds:
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation("bench.traced")
            self.span.__enter__()
            self.first, self.t_on = n, now
        elif self.first is not None and self.last is None and (
                now >= self.t_on + TRACE_SECONDS or n < 0):
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.last = len(self.driver.steps)

    def close(self):
        if self.first is not None and self.last is None:
            self(-1)


def end_to_end(win) -> dict:
    """The four served metrics over every sample of the window."""
    from mxbench.stats import percentile

    due = win.due_in_window()
    ttft = [(r.first - r.due) * 1e3 for r in due if r.first is not None]
    itl, tokens = [], 0
    for r in win.records.values():
        for j, t in enumerate(r.times):
            if win.in_window(t):
                tokens += 1
                if j:
                    itl.append((t - r.times[j - 1]) * 1e3)
    return {"ttft_p95_ms": percentile(ttft, 95),
            "itl_p50_ms": percentile(itl, 50),
            "itl_p95_ms": percentile(itl, 95),
            "output_tok_s": tokens / win.seconds}


def load(win) -> dict:
    """How the offered load sat on the batcher (for the knee sweep): slots
    busy over the window and when it opened (the warm-up has filled the
    batcher when the two agree), the share of the KV pool's token rows
    that hold a token, answer tokens asked for by the requests due in the window per
    second, requests queued when the window opened and closed, and the
    median time to first token of the requests due in each half of the
    window, which grows through the window when a backlog grows."""
    from mxbench.stats import percentile

    steps = win.window_steps()
    due = win.due_in_window()
    mid = (win.t0 + win.t1) / 2
    halves = [[(r.first - r.due) * 1e3 for r in due
               if r.first is not None and (r.due < mid) == first]
              for first in (True, False)]
    return {"offered_tok_s": sum(r.max_new for r in due) / win.seconds,
            "slots_busy_mean": (sum(s.active for s in steps) / len(steps)
                                if steps else None),
            "slots_busy_at_open": win.busy_at_open,
            "kv_pool_live_pct": (100 * sum(s.live for s in steps) / len(steps)
                                 if steps else None),
            "queued_at_open": win.queued[0], "queued_at_close": win.queued[1],
            "ttft_p50_ms_halves": [percentile(h, 50) for h in halves]}


def verdict(r, limits: dict, win) -> tuple:
    """(correct, checks): the readings of the timed path, or of the
    control in its place, each beside its limit."""
    checks = {
        "max_logit_err": {"value": r.max_logit_err,
                          "limit": limits["max_logit_err"]},
        "max_token_gap": {"value": r.max_token_gap,
                          "limit": limits["max_token_gap"]},
        "kept_rows": {"value": r.rows, "limit": 1},
        "unanswered": {"value": len(win.unanswered), "limit": 0},
    }
    correct = (r.max_logit_err <= limits["max_logit_err"]
               and r.max_token_gap <= limits["max_token_gap"]
               and r.rows >= 1 and not win.unanswered)
    return bool(correct), checks


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, *, on_chip: bool = True, fault=None,
         control: bool = False, benchmark=None, traffic_dir=None) -> dict:
    """One run; returns the result object (also printed).  With
    ``control`` the precision control stands in the program's place in
    the comparison: `correct` and `checks` are the control's, the
    program's readings go under `program_checks`.  For the tests: ``on_chip=False``
    skips the look for a TPU, the peaks table and the persistent compile
    cache; ``fault`` (a callable given the batcher before the window) breaks
    the timed path; ``benchmark`` and ``traffic_dir`` stand in for
    BENCHMARK.json and bench/traffic."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrivals per second in place of the "
                         "mix's (bench/series.py)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the precision control in the program's "
                         "place (bench/series.py)")
    args = ap.parse_args(argv)
    control = control or bool(args.control)

    spec = load_cell(args.workload, benchmark, traffic_dir)
    cell, cfg, mix = spec["cell"], spec["cfg"], spec["mix"]
    if args.rate is not None:
        mix = dict(mix, rate_per_s=args.rate)
    chips = cell["chips"]
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.serve import place
        from repro.models import build_model
        from repro.parallel.sharding import (
            collective_policy, make_rules, tree_shardings, use_rules)
        from repro.runtime.batcher import ContinuousBatcher
        from repro.runtime.lifecycle import Request, RetryPolicy
    except ImportError as e:
        raise Refused(f"the serving program is not beside the benchmark "
                      f"({e})")
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from mxbench import check, devtrace, traffic
    from mxbench import weights as W
    from mxbench.loop import Driver
    from mxbench.view import RunView

    if on_chip:
        (ROOT / ".jax_cache").mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # no eviction: it reads an access-time file beside every entry, and
        # one entry without it stops every later write
        jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    dev = devices[0]
    if on_chip and dev.platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {dev.platform}")
    if len(devices) < chips:
        raise Refused(f"{args.workload} needs {chips} chips, found "
                      f"{len(devices)}")
    peak = peaks_for(dev.device_kind) if on_chip else \
        {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ref = reference_module(cfg)
    layout = ref.layout(cfg)
    arch = arch_config(cfg)
    model = build_model(arch)
    used = devices[:chips]
    mesh = Mesh(np.array(used).reshape(1, chips), ("data", "model"))
    rules = make_rules(mesh, profile=arch.parallelism)
    abstract = model.abstract()
    mine = W.abstract(layout)
    if jax.tree.structure(abstract) != jax.tree.structure(mine) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(abstract),
                                               jax.tree.leaves(mine))):
        raise RuntimeError("the program's parameter tree differs from the "
                           "reference layout")

    seconds = args.seconds
    plan = traffic.plan(mix, seconds, args.seed, cfg["vocab_size"])
    if traffic.max_tokens(mix) > cfg["max_len"]:
        raise RuntimeError("the mix can draw requests longer than max_len")
    widths, tails = warm_shapes(cfg, mix, seconds)
    tp = collective_policy(mesh) if chips > 1 else contextlib.nullcontext()
    counter = CompileCounter().__enter__()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    with use_rules(rules), tp:
        # the pool is made whole on the first chip and then placed, so it
        # goes first, before the weights fill that chip
        batcher = ContinuousBatcher(
            model, None, batch_slots=cfg["slots"], max_len=cfg["max_len"],
            paged=True, page_size=cfg["page_size"],
            num_pages=cfg["pool_pages"], prefix_cache=cfg["prefix_cache"],
            prefill_chunk=cfg["prefill_chunk"],
            retry=RetryPolicy(max_retries=3, backoff_s=0.0))
        axes = model.make_paged_cache(batcher.pool.total_pages,
                                      batcher.page_size, mode="axes")
        batcher.cache = place(rules, batcher.cache, axes)
        jax.block_until_ready(batcher.cache)
        shardings = tree_shardings(rules, abstract, model.axes())
        params = batcher.params = W.make(layout, W.seed_key(args.seed),
                                         shardings)
        warm_up(batcher, widths, tails)
        log(f"warmed decode widths {widths}, prefill chunks {tails}: "
            f"{counter.programs} programs built, {counter.cache_hits} of "
            f"them from the cache, in {counter.seconds:.1f} s")
        capture = check.Capture(batcher, plan)
        if fault is not None:
            fault(batcher)
        driver = Driver(batcher, lambda p: Request(
            rid=p.rid, prompt=p.prompt, max_new=p.max_new))
        tracer = Tracer(driver, seconds, trace_dir) if args.trace else None
        opened = {}

        def at_open(t0):
            # set-up ends here: build, warm-up of every shape, and the
            # untimed traffic that fills the batcher
            opened.update(setup_s=t0 - T_START, programs=counter.programs)
            if tracer:
                tracer.t0 = t0

        win = driver.run(plan, seconds, warm_s=mix.get("warm_s", 0.0),
                         hook=tracer, at_open=at_open)
        if tracer:
            tracer.close()
        in_window = counter.programs - opened["programs"]
        jax.block_until_ready(batcher.cache)
        mem = [d.memory_stats() or {} for d in used]
        peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in mem)
        capture.unhook()
        done = {r.rid: r.prompt_len + len(r.times) for r in win.finished()}
        sample = check.draw_sample(done, args.seed)
        capture.collect(sample)
        outputs = {rid: list(batcher.finished[rid].output) for rid in sample}
        batcher.cache = batcher.params = None
        del batcher, params, driver
        gc.collect()
    counter.__exit__(None, None, None)
    log(f"window {win.seconds:.2f} s, {len(win.steps)} steps, drain "
        f"{win.drain_s:.2f} s; {in_window} programs built in the window")

    t_check = time.perf_counter()
    prompts = {p.rid: p.prompt for p in plan}
    read = check.readings(ref, cfg, layout, args.seed, prompts, outputs,
                          capture, cfg["max_len"], used, control=control)
    log(f"reference check {time.perf_counter() - t_check:.1f} s over "
        f"{read['program'].tokens} served tokens, {read['program'].rows} "
        f"kept rows")

    due = win.due_in_window()
    failed = len(win.unanswered) + sum(
        1 for r in due if r.finish_reason not in (None, "max_new", "eos",
                                                  "max_len"))
    limits = cfg["checks"]
    correct, checks = verdict(read["control" if control else "program"],
                              limits, win)

    result = {"correct": bool(correct), "attempted": len(due),
              "failed": failed, "metrics": {}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": int(peak_bytes)}
    if args.trace:
        trace = devtrace.load(trace_dir) if tracer.first is not None \
            else None
        view = RunView(cfg, chips, peak, win, in_window, trace,
                       (tracer.first or 0, tracer.last or 0))
        for m in spec["per_layer"]:
            v = metric_reader(m["name"]).read(view)
            if v is not None and math.isfinite(v):
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if trace is not None and trace.devices:
            lo, hi = devtrace.window(trace)
            busy = devtrace.busy(trace)
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = (hi - lo) * 1e-9
            first = sorted(trace.devices)[0]
            result["breakdown"] = {
                "device_ops": [list(x) for x in devtrace.top_ops(trace)],
                "idle_gaps": [list(x) for x in
                              devtrace.idle_gaps(trace, first)]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = end_to_end(win)
        e2e["setup_s"] = opened["setup_s"]
        for m in spec["end_to_end"]:
            v = e2e.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    result["device"] = device
    result["load"] = load(win)
    if control:
        result["program_checks"] = verdict(read["program"], limits, win)[1]
        result["token_fault"] = read["token_fault"].__dict__
        result["control_bf16"] = read["control_bf16"].__dict__
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    try:
        main()
    except Refused as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        sys.exit(3)
