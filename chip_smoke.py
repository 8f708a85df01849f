"""Smoke run of the serving path on TPU: llama3.2-1b at its published width,
served through the repository's Pallas kernels.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the (1, 4) tensor-parallel mesh,
                                    # against one of its four chips

One chip: 8 requests (512-token prompts of which 384 tokens are a shared
system prompt, 32 generated tokens each) go through `repro.launch.serve`'s
continuous batcher over paged KV, with the prefix cache and 128-token
chunked prefill; parameters and prompts come from seeds.  The script then
counts the Pallas kernels in the compiled decode and prefill programs, and
compares the served path's logits, teacher-forced over one prompt and 4
decode steps, with an XLA reference at "highest" matmul precision on the
same chip.

Four chips: the same requests on a (1, 4) mesh under the ring collective
policy, against the same model on one of those chips in the same process;
it prints the parameter bytes on each device, the logit agreement and how
many tensor-parallel projections took the ring.

A failed phase exits nonzero.  The last line of standard output is one JSON
object, {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Without a TPU, or without the repository beside it, the script exits
nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SERVE_FLAGS = [
    "--arch", "llama3.2-1b", "--batch", "4", "--prompt-len", "512",
    "--gen", "32", "--paged", "--prefix-cache", "--prefill-chunk", "128",
    "--page-size", "16",
]
N_DECODE = 4  # teacher-forced decode steps after the prompt
# Max |logit| error allowed against the reference.  Weights are f32 and the
# kernels accumulate in f32; what may differ is the MXU's operand rounding
# (bf16 passes, 2^-9 relative per product) and the summation order, over 16
# layers.  The logits have unit scale (tied table at std 0.02, d_model
# 2048), so 5e-2 is 5% of that scale — far below it, any layout or masking
# fault would show.
LOGIT_TOL = 5e-2


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while active."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


def _param_bytes_by_device(params) -> dict:
    import jax

    out = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return out


def _serve(serve, model, cfg, params, args, rules):
    """The launcher's own continuous-batching run; checks every request."""
    from repro.parallel.sharding import use_rules

    with use_rules(rules), _CompileClock() as clock:
        batcher, finished, wall = serve.run_continuous(
            model, cfg, params, args, rules)
    reasons = {}
    for r in finished.values():
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    n_req = 2 * args.batch
    if len(finished) != n_req or reasons != {"max_new": n_req}:
        _fail(f"expected {n_req} requests finished by max_new, got {reasons}")
    if any(len(r.output) != args.gen for r in finished.values()):
        _fail("a request emitted the wrong number of tokens")
    return batcher, finished, reasons, wall, clock.seconds


def _teacher_forced(model, params, tokens, args, rules):
    """The served path's logits for ``tokens`` (the batcher's jitted steps'
    functions, at the served shapes): the prompt through chunked prefill
    into pages, then one decode step per remaining token, in slot 0 of
    ``args.batch`` slots.  Returns (logits (T, vocab) f32, compiled decode
    program, compiled prefill program)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import place
    from repro.parallel.sharding import use_rules
    from repro.runtime.kv_pages import PagePool

    B, ps, chunk = args.batch, args.page_size, args.prefill_chunk
    plen = args.prompt_len
    width = -(-(plen + args.gen) // ps)
    pool = PagePool((B + 2) * width, ps)  # the served pool's size
    pool.reserve(0, len(tokens))
    with use_rules(rules):
        cache = place(rules,
                      model.make_paged_cache(pool.total_pages, ps,
                                             dtype=jnp.float32),
                      model.make_paged_cache(pool.total_pages, ps,
                                             mode="axes"))
        prefill = jax.jit(model.prefill_step_paged)
        decode = jax.jit(model.decode_step_paged)
        table1 = jnp.asarray(pool.page_table(1, width))
        logits = []
        for t in range(0, plen, chunk):
            args_p = (params, jnp.asarray(tokens[None, t:t + chunk]), cache,
                      jnp.asarray([t], np.int32), table1)
            out, cache = prefill(*args_p)
            logits.append(out[0])
            pool.set_length(0, t + chunk)
        for t in range(plen, len(tokens)):
            step_tokens = np.zeros((B, 1), np.int32)
            step_tokens[0, 0] = tokens[t]
            index = np.zeros((B,), np.int32)
            index[0] = t
            pool.set_length(0, t + 1)
            args_d = (params, jnp.asarray(step_tokens), cache,
                      jnp.asarray(index), jnp.asarray(pool.page_table(B, width)),
                      jnp.asarray(pool.lengths(B)))
            out, cache = decode(*args_d)
            logits.append(out[0])
        compiled_d = decode.lower(*args_d).compile()
        compiled_p = prefill.lower(*args_p).compile()
    return jnp.concatenate(logits, axis=0), compiled_d, compiled_p


def one_chip(serve, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import ops
    from repro.core.hlo_census import kernel_launches

    pol = ops.current_policy()
    print(f"device: {dev.platform} {dev.device_kind}; kernel policy: "
          f"{pol.backend}, interpret={pol.interpret}")
    if pol.backend != "pallas_mx" or pol.interpret:
        _fail("the default policy on a TPU must be compiled pallas_mx")
    args = serve.parse_args(SERVE_FLAGS)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    cfg, model, rules, params = serve.load_model(args, mesh=mesh)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads x {cfg.hd}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}; params "
          f"{sum(x.size for x in jax.tree.leaves(params))} "
          f"({nbytes} bytes f32, PRNGKey(0))")
    print("prefill attention: XLA gather (kernels.ref.paged_prefill_ref); "
          "it has no Pallas kernel yet")

    batcher, finished, reasons, wall, compile_s = _serve(
        serve, model, cfg, params, args, rules)
    ps = batcher.prefix_stats()
    print(f"served {len(finished)} requests ({args.prompt_len}-token prompts, "
          f"{args.gen} generated each): finish reasons {reasons}; prefix "
          f"cache {ps['hits']}/{ps['hits'] + ps['misses']} admissions hit, "
          f"{ps['tokens_saved']} prompt tokens skipped")
    if ps["hits"] < 1:
        _fail("no request hit the shared system prompt")
    print(f"smoke timings, not metrics: compile {compile_s:.1f} s, "
          f"serve wall {wall:.1f} s (compiles included)")

    req = finished[0]
    tokens = np.concatenate([req.prompt, req.output[:N_DECODE]]).astype(np.int32)
    got, compiled_d, compiled_p = _teacher_forced(model, params, tokens,
                                                  args, rules)
    for name, compiled in (("decode", compiled_d), ("prefill", compiled_p)):
        census = dict(kernel_launches(compiled.as_text()))
        print(f"tpu_custom_call in the compiled {name} program: {census}")
        need = ("mx_matmul_fused", "mx_flash_decode") if name == "decode" \
            else ("mx_matmul_fused",)
        if any(not census.get(k) for k in need):
            _fail(f"{name} program lacks one of {need}")

    with ops.use_policy(ops.MXPolicy(backend="xla")), \
            jax.default_matmul_precision("highest"):
        ref = jax.jit(model.__call__)(params, jnp.asarray(tokens[None]))[0][0]
    diff = jnp.max(jnp.abs(got - ref), axis=-1)
    err = float(jnp.max(diff))
    scale = float(jnp.max(jnp.abs(ref)))
    greedy = np.asarray(jnp.argmax(ref[args.prompt_len - 1:-1], axis=-1))
    print(f"logits vs XLA reference (highest precision, same chip), request "
          f"0 over {len(tokens)} positions (prompt + {N_DECODE} decode "
          f"steps): max abs error {err:.3e} (prefill positions "
          f"{float(jnp.max(diff[:args.prompt_len])):.3e}, decode steps "
          f"{float(jnp.max(diff[args.prompt_len:])):.3e}; max |logit| "
          f"{scale:.3f}; tolerance {LOGIT_TOL}); reference greedy tokens "
          f"match the served ones at "
          f"{int((greedy == req.output[:N_DECODE]).sum())}/{N_DECODE} "
          f"decode steps")
    if not err <= LOGIT_TOL:
        _fail(f"logit error {err} above {LOGIT_TOL}")


def four_chips(serve, dev) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.parallel.sharding import collective_policy

    if len(jax.devices()) < 4:
        _fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    args = serve.parse_args(SERVE_FLAGS)
    devs = np.array(jax.devices()[:4])
    mesh4 = Mesh(devs.reshape(1, 4), ("data", "model"))
    mesh1 = Mesh(devs[:1].reshape(1, 1), ("data", "model"))

    cfg, model, rules4, params4 = serve.load_model(args, mesh=mesh4)
    per_dev = _param_bytes_by_device(params4)
    total = sum(x.nbytes for x in jax.tree.leaves(params4))
    print(f"{cfg.name} on a (1, 4) mesh of {dev.device_kind}: param bytes "
          f"per device {per_dev} of {total} "
          f"(shares {[round(v / total, 3) for v in per_dev.values()]})")
    with collective_policy(mesh4) as coll:
        _, fin4, _, wall4, c4 = _serve(serve, model, cfg, params4, args,
                                       rules4)
        got4, _, _ = _teacher_forced(
            model, params4,
            np.concatenate([fin4[0].prompt, fin4[0].output[:N_DECODE]]),
            args, rules4)
    routed = {f"{m}/{how}": n for (m, how), n in sorted(coll.routed.items())}
    n_ring = sum(n for (_, how), n in coll.routed.items() if how == "ring")
    print(f"TP projections traced (per program, a scanned layer counts "
          f"once): {n_ring} took the ring, "
          f"{sum(coll.routed.values()) - n_ring} fell back; {routed}")
    if not n_ring:
        _fail("no projection took the ring")

    _, _, rules1, params1 = serve.load_model(args, mesh=mesh1)
    _, fin1, _, wall1, c1 = _serve(serve, model, cfg, params1, args, rules1)
    got1, _, _ = _teacher_forced(
        model, params1,
        np.concatenate([fin4[0].prompt, fin4[0].output[:N_DECODE]]),
        args, rules1)
    same = sum(int(np.sum(np.asarray(fin4[i].output) == fin1[i].output))
               for i in fin4)
    n_tok = sum(len(r.output) for r in fin4.values())
    err = float(np.max(np.abs(np.asarray(got4) - np.asarray(got1))))
    print(f"4 chips vs 1 chip: {same}/{n_tok} served tokens identical; "
          f"teacher-forced logits max abs difference {err:.3e} "
          f"(tolerance {LOGIT_TOL})")
    print(f"smoke timings, not metrics: 4 chips compile {c4:.1f} s, serve "
          f"wall {wall4:.1f} s; 1 chip compile {c1:.1f} s, serve wall "
          f"{wall1:.1f} s")
    if not err <= LOGIT_TOL:
        _fail(f"4-chip logits differ from 1-chip by {err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the (1, 4) mesh phase against one chip")
    opts = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository is not beside this script ({e})",
              file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    t0 = time.time()
    (four_chips if opts.chips == 4 else one_chip)(serve, dev)
    print(f"chip_smoke done in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
