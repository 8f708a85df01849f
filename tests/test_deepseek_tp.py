"""deepseek-67b's block (untied head, no bias, heads divisible by four)
served through the paged `ContinuousBatcher` with chunked prefill on a
(1, 4) tensor-parallel mesh, every projection on the ring and the Pallas
kernels in interpret mode, against the benchmark's plain f32 reference on
weights drawn from a seed.

The mesh needs four virtual CPU devices, and XLA_FLAGS must be set before
jax starts, so the serving runs in one subprocess; its result comes back
as one JSON line that the tests read.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

_SERVE_CODE = r"""
import os, re, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = ["bench", "src"]
import jax, numpy as np
from jax.sharding import Mesh

from mxbench import check, traffic
from mxbench import weights as W
from repro.configs import get_config
from repro.core import ops
from repro.launch.serve import place
from repro.models import build_model
from repro.parallel.sharding import (
    collective_policy, make_rules, tree_shardings, use_rules)
from repro.runtime.batcher import ContinuousBatcher
from repro.runtime.lifecycle import Request
import run

SEED = 2147483011
arch = get_config("deepseek-67b-smoke")
cfg = {"reference": "dense_decoder", "hidden_size": arch.d_model,
       "num_hidden_layers": arch.n_layers,
       "num_attention_heads": arch.n_heads,
       "num_key_value_heads": arch.n_kv_heads, "head_dim": arch.hd,
       "intermediate_size": arch.d_ff, "vocab_size": arch.vocab,
       "tie_word_embeddings": arch.tie_embeddings,
       "attention_bias": arch.qkv_bias, "rope_theta": arch.rope_theta}
assert not cfg["tie_word_embeddings"] and not cfg["attention_bias"]
assert cfg["num_attention_heads"] % 4 == 0 == cfg["num_key_value_heads"] % 4
ref = run.reference_module(cfg)
layout = ref.layout(cfg)
model = build_model(arch)
devices = jax.devices()[:4]
mesh = Mesh(np.array(devices).reshape(1, 4), ("data", "model"))
rules = make_rules(mesh, profile=arch.parallelism)
MAX_LEN, PS, CHUNK = 32, 4, 8
# prompt lengths: one chunk and a tail, two whole chunks, a lone tail
plan = [traffic.Planned(rid=i, prompt=np.random.default_rng([SEED, i])
                        .integers(0, cfg["vocab_size"], n, dtype=np.int32),
                        max_new=m)
        for i, (n, m) in enumerate([(13, 5), (17, 4), (6, 6)])]
with use_rules(rules), collective_policy(mesh) as coll, ops.use_policy(
        ops.MXPolicy(backend="pallas_mx", interpret=True)):
    batcher = ContinuousBatcher(model, None, batch_slots=2, max_len=MAX_LEN,
                                paged=True, page_size=PS, prefill_chunk=CHUNK)
    batcher.cache = place(rules, batcher.cache, model.make_paged_cache(
        batcher.pool.total_pages, PS, mode="axes"))
    batcher.params = W.make(layout, W.seed_key(SEED), tree_shardings(
        rules, model.abstract(), model.axes()))
    capture = check.Capture(batcher, plan)
    for p in plan:
        batcher.submit(Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new))
    while batcher.queue or batcher.active:
        batcher.step()
    capture.unhook()
    capture.collect([p.rid for p in plan])
    outputs = {p.rid: list(batcher.finished[p.rid].output) for p in plan}
    routed = {f"{mode} {path}": n for (mode, path), n in coll.routed.items()}
    B = batcher.B
    low = batcher._step.lower(
        batcher.params, np.zeros((B, 1), np.int32), batcher.cache,
        np.zeros((B,), np.int32), batcher.pool.page_table(B, 2),
        batcher.pool.lengths(B))
    hlo = low.as_text(debug_info=True)
    scopes = {s: hlo.count(s) for s in
              ("tp.allgather", "tp.reduce_scatter", "tp.per_device")}
    module = re.search(r"module @(\w+)", hlo).group(1)
    batcher.cache = batcher.params = None
read = check.readings(ref, cfg, layout, SEED, {p.rid: p.prompt for p in plan},
                      outputs, capture, MAX_LEN, devices, control=True)
print("RESULT " + json.dumps({
    "routed": routed, "scopes": scopes, "module": module,
    "rows": read["program"].rows,
    "served": {str(k): len(v) for k, v in outputs.items()},
    **{name: {"max_logit_err": r.max_logit_err,
              "max_token_gap": r.max_token_gap}
       for name, r in read.items()}}))
"""

# f32 everywhere: the program sums its ring partials and kernel tiles in
# another order than the reference's einsums, which moves a logit of
# magnitude ~1 by a few float32 ulps (it reads ~3e-6 here).  The limit
# leaves ~7x room above that and stays below the precision one step under
# f32: the reference at `high` (three bf16 passes) reads ~7e-5 and one bf16
# pass ~4e-2, and both must fail it.
MAX_LOGIT_ERR = 2e-5
# a served token is the program's argmax; the reference's argmax can only
# differ from it on a near-tie, by less than twice the logit error
MAX_TOKEN_GAP = 2 * MAX_LOGIT_ERR


@pytest.fixture(scope="module")
def served():
    r = subprocess.run(
        [sys.executable, "-c", _SERVE_CODE], capture_output=True, text=True,
        timeout=900, env={**os.environ, "PYTHONPATH": "src"}, cwd=ROOT)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, f"stdout={r.stdout[-2000:]}\nstderr={r.stderr[-3000:]}"
    return json.loads(lines[-1][len("RESULT "):])


def test_tp_serving_matches_the_reference(served):
    # every request served to its max_new, first and last rows kept
    assert served["served"] == {"0": 5, "1": 4, "2": 6}
    assert served["rows"] == 6
    prog = served["program"]
    assert prog["max_logit_err"] <= MAX_LOGIT_ERR, prog
    assert prog["max_token_gap"] <= MAX_TOKEN_GAP, prog
    # the reference at `high`, or at one bf16 pass, in the program's place
    # fails the same limit
    assert served["control"]["max_logit_err"] > MAX_LOGIT_ERR, served
    assert served["control_bf16"]["max_logit_err"] > MAX_LOGIT_ERR, served
    # and a served token altered where it was produced fails the gap
    assert served["token_fault"]["max_token_gap"] > MAX_TOKEN_GAP, served


def test_every_tp_projection_rides_the_ring(served):
    routed = served["routed"]
    assert routed.get("allgather ring", 0) > 0, routed
    assert routed.get("reduce_scatter ring", 0) > 0, routed
    assert not any(k.endswith("fallback") for k in routed), routed


def test_ring_bodies_carry_their_named_scopes(served):
    # a profile attributes the rings' device ops by these scopes; the
    # decode program keeps its module name, which the trace readers match
    assert all(n > 0 for n in served["scopes"].values()), served["scopes"]
    assert served["module"] == "jit_step_paged", served["module"]


def test_serve_prints_the_tp_routes():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "deepseek-67b", "--smoke", "--batch", "2", "--prompt-len", "8",
         "--gen", "3", "--paged", "--page-size", "4", "--prefill-chunk", "4"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": "src",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    routes = [ln for ln in r.stdout.splitlines() if "tp routes:" in ln]
    assert len(routes) == 1, r.stdout[-2000:]
    assert "allgather ring" in routes[0] and "reduce_scatter ring" in routes[0]
    assert "fallback" not in routes[0], routes[0]
