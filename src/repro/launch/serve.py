"""Serving launcher: chunked prefill + greedy decode with a KV cache.

CPU smoke examples:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --batch 4 --prompt-len 16 --gen 16 --prefill-chunk 8
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --paged --page-size 8
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --prefix-cache --prefill-chunk 8   # shared system prompt across requests
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --prefix-cache --chaos --fault-rate 0.1 --chaos-seed 0
      # fault-injected serving: typed finish reasons + per-step health
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --speculate 4 --draft ngram --prefill-chunk 8
      # speculative decoding: K drafts verified per launch, exact outputs
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..launch.compile_cache import enable_compile_cache
from ..launch.mesh import make_mesh
from ..launch.steps import make_chunked_prefill_step, make_serve_step
from ..models import build_model
from ..parallel.sharding import (
    collective_policy, current_collectives, make_rules, tree_shardings,
    use_rules,
)


def device_label() -> str:
    """What the rates were measured on, as JAX reports it."""
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}"


def place(rules, tree, axes):
    """Put a pytree on the rules' mesh, each leaf sharded by its logical
    axes (the same mapping `launch/train.py` places parameters with)."""
    return jax.device_put(tree, tree_shardings(rules, tree, axes))


def load_model(args, mesh=None):
    """Config, model, sharding rules and parameters for ``args``.  The
    parameters come from PRNGKey(0), created directly in their shardings
    on ``mesh`` (default: a (1, n) tensor-parallel mesh over every device)
    — no device holds a full copy on the way."""
    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    model = build_model(cfg)
    if mesh is None:
        mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
    rules = make_rules(mesh, profile=cfg.parallelism)
    with use_rules(rules):
        shardings = tree_shardings(rules, model.abstract(), model.axes())
        params = jax.jit(model.init, out_shardings=shardings)(
            jax.random.PRNGKey(0))
    return cfg, model, rules, params


def run_continuous(model, cfg, params, args, rules):
    """Continuous batching: 2x requests stream through --batch decode slots
    (runtime/batcher.py); returns (batcher, finished requests, wall s).
    The KV cache is placed on the rules' mesh like the parameters.
    --paged swaps the dense (slots, max_len) cache for the page-pool
    backend (runtime/kv_pages + kernels/mx_flash_decode).  --prefix-cache
    additionally shares already-prefilled prompt prefixes across requests
    (every request gets a common system prompt of 3/4 of --prompt-len
    here, so hits are visible).  --chaos additionally threads a seeded
    `ChaosInjector` through every step (transient step failures, one-slot
    logit poisoning, pool-pressure episodes, latency spikes)."""
    from ..runtime.batcher import ContinuousBatcher, Request
    from ..runtime.lifecycle import ChaosConfig, ChaosInjector, RetryPolicy

    drafter = None
    if args.speculate:
        if args.draft == "ngram":
            from ..runtime.speculative import NGramDrafter

            drafter = NGramDrafter()
        else:
            # a small draft model sharing the token space: any arch id works
            # as long as its vocab matches the target's
            from ..runtime.speculative import DraftModelProposer

            dcfg = get_config(args.draft + ("-smoke" if args.smoke else ""))
            if dcfg.vocab != cfg.vocab:
                raise SystemExit(
                    f"--draft {args.draft}: draft vocab {dcfg.vocab} != "
                    f"target vocab {cfg.vocab}")
            dmodel = build_model(dcfg)
            dparams = dmodel.init(jax.random.PRNGKey(1))
            drafter = DraftModelProposer(dmodel, dparams)

    B = args.batch
    max_len = args.max_len or (args.prompt_len + args.gen)
    kv_quant = None
    if args.kv_cache == "int8":
        from ..core.precision import QuantSpec

        kv_quant = QuantSpec("int8", "tile")
    # the prefix cache keeps pinned pages resident across requests: size the
    # pool above the dense rectangle so pins don't starve admissions
    num_pages = None
    if args.prefix_cache:
        num_pages = (B + 2) * -(-max_len // args.page_size)
    chaos = None
    if args.chaos:
        chaos = ChaosInjector(ChaosConfig(
            seed=args.chaos_seed,
            step_failure_rate=args.fault_rate,
            poison_rate=args.fault_rate / 4,
            latency_spike_rate=args.fault_rate,
            pool_pressure_rate=args.fault_rate / 2 if args.paged else 0.0,
            pool_pressure_pages=2,
            # SDC bit flips only land where the ABFT guard can catch them
            bitflip_rate=args.fault_rate if args.abft else 0.0,
        ))
    batcher = ContinuousBatcher(
        model, params, batch_slots=B, max_len=max_len,
        paged=args.paged, page_size=args.page_size, kv_quant=kv_quant,
        num_pages=num_pages, prefix_cache=args.prefix_cache,
        prefill_chunk=args.prefill_chunk if args.paged else 0,
        chaos=chaos, retry=RetryPolicy(max_retries=3, backoff_s=0.0),
        speculate=args.speculate, drafter=drafter, abft=args.abft,
    )
    if args.paged:
        axes = model.make_paged_cache(batcher.pool.total_pages,
                                      batcher.page_size, mode="axes",
                                      kv_quant=kv_quant)
    else:
        axes = model.make_cache(B, max_len, mode="axes")
    batcher.cache = place(rules, batcher.cache, axes)
    rng = np.random.default_rng(0)
    n_req = 2 * B
    # a shared system prompt (75% of prompt_len) + per-request tails: the
    # workload shape the prefix cache exists for
    sys_prompt = rng.integers(0, cfg.vocab, max(1, (3 * args.prompt_len) // 4))
    t0 = time.time()
    for i in range(n_req):
        if args.prefix_cache:
            tail = rng.integers(0, cfg.vocab,
                                max(1, args.prompt_len - len(sys_prompt)))
            prompt = np.concatenate([sys_prompt, tail]).astype(np.int32)
        else:
            plen = int(rng.integers(2, args.prompt_len + 1))
            prompt = rng.integers(0, cfg.vocab, plen).astype(np.int32)
        # under chaos, stagger priorities and give every request a generous
        # step deadline so expiry/preemption paths are visible end to end
        kw = {}
        if args.chaos:
            kw = dict(priority=i % 2,
                      deadline_steps=8 * (args.prompt_len + args.gen))
        batcher.submit(Request(rid=i, prompt=prompt, max_new=args.gen, **kw))
    finished = batcher.run_to_completion()
    return batcher, finished, time.time() - t0


def step_report(batcher, finished) -> list:
    """The step and request records as report lines: mean host ms of each
    phase of a step that ran the device, queue wait (first admission less
    submission, from the requests' wall stamps) p50/p95, the prompt tokens
    chunked prefill pushed through in a step (p95), and the mean work of a
    step: admissions and prefill chunks, and for a step that ran the
    device its table width and the pages its attention read, as a share of
    slots x width (paged), and the tokens it emitted."""
    steps = [h for h in batcher.health if h.active]
    if not steps:
        return []
    phases = ("admit", "prepare", "dispatch", "wait", "emit")
    mean_ms = {p: 1e3 * float(np.mean([getattr(h, p + "_s") for h in steps]))
               for p in phases + ("dt",)}
    waits = []
    for r in finished.values():
        kinds = [k for k, _ in r.events]
        if "admitted" in kinds:
            waits.append(1e3 * (r.event_times[kinds.index("admitted")]
                                - r.event_times[kinds.index("submitted")]))
    lines = [f"  step phases, mean ms over {len(steps)} device steps: "
             + ", ".join(f"{p} {mean_ms[p]:.2f}" for p in phases)
             + f" (whole step {mean_ms['dt']:.2f})"]
    if waits:
        p50, p95 = np.percentile(waits, [50, 95])
        lines.append(f"  queue wait: p50 {p50:.2f} ms, p95 {p95:.2f} ms "
                     f"over {len(waits)} admitted requests")
    prefill = np.percentile([h.prefill_tokens for h in batcher.health], 95)
    lines.append(f"  prefill tokens per step: p95 {prefill:.0f} "
                 f"({batcher.prefill_tokens} in {batcher.prefill_launches} "
                 f"launches)")
    mean = {f: float(np.mean([getattr(h, f) for h in batcher.health]))
            for f in ("admitted", "prefill_chunks")}
    mean.update({f: float(np.mean([getattr(h, f) for h in steps]))
                 for f in ("width", "emitted", "attn_pages")})
    grid = batcher.B * mean["width"]
    lines.append(f"  per step: {mean['admitted']:.2f} admitted, "
                 f"{mean['prefill_chunks']:.2f} prefill chunks; per device "
                 f"step: {mean['emitted']:.2f} tokens emitted"
                 + (f", table width {mean['width']:.1f} pages, attention "
                    f"reads {mean['attn_pages']:.1f} pages "
                    f"({mean['attn_pages'] / grid:.3f} of slots x width)"
                    if batcher.paged else ""))
    return lines


def _run_continuous(model, cfg, params, args, rules) -> int:
    """`run_continuous`, then its report: the step records (`step_report`),
    page occupancy (--paged), the prefix index's hit rate and pages shared
    (--prefix-cache), the per-step health record (--chaos): typed finish
    reasons, retries, preempt/resume counts, quarantines, straggler
    flags."""
    batcher, finished, wall = run_continuous(model, cfg, params, args, rules)
    B = args.batch
    total = sum(len(r.prompt) + len(r.output) for r in finished.values())
    mode = "paged" if args.paged else "dense"
    if args.prefix_cache:
        mode += "+prefix"
    if args.chaos:
        mode += "+chaos"
    if args.speculate:
        mode += f"+spec{args.speculate}"
    if args.abft:
        mode += "+abft"
    print(f"continuous batching [{mode} cache]: {len(finished)} requests "
          f"through {B} slots; {total / wall:.1f} tok/s "
          f"({device_label()})")
    for line in step_report(batcher, finished):
        print(line)
    if args.paged:
        st = batcher.pool_stats()
        print(f"  pages: {st.pages_in_use} in use / {st.num_pages} pool "
              f"(high water {st.high_water}, page_size {st.page_size}, "
              f"peak utilization {st.high_water / st.num_pages:.2f})")
        coll = current_collectives()
        if coll is not None:
            # per projection kind, ring against fallback: a silent
            # fallback to replicated operands shows here
            print("  tp routes: " + ", ".join(
                f"{mode} {path} {n}"
                for (mode, path), n in sorted(coll.routed.items())))
    if args.speculate:
        sp = batcher.spec_stats()
        print(f"  speculation [k={args.speculate}, draft {args.draft}]: "
              f"{sp['accepted']}/{sp['drafted']} drafts accepted "
              f"({sp['acceptance_rate']:.0%}), "
              f"{sp['emitted']} tokens over {sp['launches']} launches "
              f"({sp['tokens_per_launch']:.2f} tok/launch)")
    if args.prefix_cache:
        ps = batcher.prefix_stats()
        print(f"  prefix cache: {ps['hits']}/{ps['hits'] + ps['misses']} "
              f"admissions hit ({ps['hit_rate']:.0%}), "
              f"{ps['tokens_saved']} prefill tokens skipped, "
              f"{ps['pages_reused']} pages reused now "
              f"(peak shared {ps['shared_high_water']}), "
              f"{ps['cow_copies']} COW copies, "
              f"{ps['evicted_pages']} pages evicted")
    if args.abft:
        hs = batcher.health_summary()
        flips = (hs["chaos"] or {}).get("bitflips_injected", 0) \
            if args.chaos else 0
        print(f"  abft: {hs['abft']['sdc_detected']} SDC detected / "
              f"{hs['abft']['sdc_corrected']} corrected "
              f"({flips} bit flips injected)")
    if args.chaos:
        hs = batcher.health_summary()
        print(f"  chaos [seed {args.chaos_seed}]: "
              f"{hs['chaos']['failures_injected']} step failures "
              f"({hs['retries']} retries), "
              f"{hs['chaos']['poisons_injected']} poisons "
              f"({hs['quarantined']} quarantined), "
              f"{hs['preemptions']} preemptions / {hs['resumes']} resumes "
              f"(mean resume latency "
              f"{hs['resume_latency_steps_mean']:.1f} steps), "
              f"{hs['stragglers']} straggler steps")
        reasons = hs["finish_reasons"]
        print("  finish reasons: "
              + ", ".join(f"{k}={v}" for k, v in sorted(reasons.items())))
        slow = max(batcher.health, key=lambda h: h.dt_s)
        print(f"  health: {hs['steps']} steps recorded; slowest step "
              f"{slow.step} at {slow.dt_s * 1e3:.1f}ms "
              f"(active {slow.active}, queued {slow.queued})")
    for rid in sorted(finished)[:2]:
        print(f"  req {rid}: {finished[rid].output[:8]}")
    return 0


def _run_disagg(model, cfg, params, args) -> int:
    """Disaggregated serving (runtime/disagg.py): --disagg N prefill
    workers fill KV pages and hand finished requests to the decode pool by
    shipping the page table (shared pool: incref-publish-mount, zero
    copies; --disagg-migrate: disjoint pools with explicit page
    migration).  --chaos adds worker kills, hangs, and handoff drops on
    top of the decode-side fault mix; the engine heals via heartbeat
    detection, page-republish recovery, rerouting, and degraded-mode
    decode-side prefill."""
    from ..runtime.disagg import DisaggEngine
    from ..runtime.lifecycle import ChaosConfig, ChaosInjector, Request, \
        RetryPolicy

    B = args.batch
    max_len = args.max_len or (args.prompt_len + args.gen)
    kv_quant = None
    if args.kv_cache == "int8":
        from ..core.precision import QuantSpec

        kv_quant = QuantSpec("int8", "tile")
    chaos = None
    if args.chaos:
        chaos = ChaosInjector(ChaosConfig(
            seed=args.chaos_seed,
            step_failure_rate=args.fault_rate / 4,
            worker_kill_rate=args.fault_rate / 8,
            worker_hang_rate=args.fault_rate / 4,
            handoff_drop_rate=args.fault_rate,
        ))
    eng = DisaggEngine(
        model, params, prefill_workers=args.disagg, batch_slots=B,
        max_len=max_len, page_size=args.page_size,
        prefill_chunk=args.prefill_chunk or 8,
        shared_pool=not args.disagg_migrate, kv_quant=kv_quant,
        chaos=chaos, retry=RetryPolicy(max_retries=3, backoff_s=0.0),
    )
    rng = np.random.default_rng(0)
    n_req = 4 * B
    sys_prompt = rng.integers(0, cfg.vocab, max(1, (3 * args.prompt_len) // 4))
    t0 = time.time()
    for i in range(n_req):
        if i % 2 == 0:  # half the trace shares a system prompt
            tail = rng.integers(0, cfg.vocab,
                                max(1, args.prompt_len - len(sys_prompt)))
            prompt = np.concatenate([sys_prompt, tail]).astype(np.int32)
        else:
            plen = int(rng.integers(2, args.prompt_len + 1))
            prompt = rng.integers(0, cfg.vocab, plen).astype(np.int32)
        eng.submit(Request(rid=i, prompt=prompt, max_new=args.gen))
    finished = eng.run_to_completion()
    wall = time.time() - t0
    total = sum(len(r.prompt) + len(r.output) for r in finished.values())
    s = eng.summary()
    mode = "shared-pool" if eng.shared_pool else "page-migration"
    if args.chaos:
        mode += "+chaos"
    print(f"disagg serving [{mode}]: {len(finished)} requests, "
          f"{args.disagg} prefill workers -> {B} decode slots; "
          f"{total / wall:.1f} tok/s ({device_label()})")
    print(f"  handoffs: {s['handoffs_completed']} completed "
          f"({s['migrated_pages']} pages migrated, "
          f"{s['handoff_drops']} drops, {s['reroutes']} reroutes), "
          f"{s['recoveries']} worker recoveries, "
          f"{s['degraded_forwards']} degraded-mode forwards")
    print("  workers: " + ", ".join(
        f"w{w['wid']}={w['state']}{'(suspected)' if w['suspected'] else ''}"
        f" x{w['launches']}" for w in s["workers"]))
    reasons = s["batcher"]["finish_reasons"]
    print("  finish reasons: "
          + ", ".join(f"{k}={v}" for k, v in sorted(reasons.items())))
    if chaos is not None:
        cs = chaos.summary()
        print(f"  chaos [seed {args.chaos_seed}]: "
              f"{cs['worker_kills_injected']} worker kills, "
              f"{cs['worker_hangs_injected']} hangs, "
              f"{cs['handoff_drops_injected']} handoff drops, "
              f"{cs['failures_injected']} step failures")
    for rid in sorted(finished)[:2]:
        print(f"  req {rid}: {finished[rid].output[:8]}")
    return 0


def parse_args(argv=None) -> argparse.Namespace:
    """The launcher's flags, with the implied ones filled in."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: 2x requests stream through "
                         "--batch decode slots (runtime/batcher.py)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (implies --continuous): page-pool "
                         "allocator + split-KV flash decode; decode bytes "
                         "scale with live tokens, not max_len")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share already-prefilled prompt prefixes across "
                         "requests (implies --paged): matched spans mount "
                         "as refcounted shared pages, COW on intra-page "
                         "divergence, zero prefill GEMMs for the hit span")
    ap.add_argument("--kv-cache", choices=("f32", "int8"), default="f32",
                    help="paged-cache payload dtype (int8 stores per-row "
                         "scale pages via kernels/quant)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injected serving (implies --continuous): "
                         "seeded step failures, logit poisoning, pool "
                         "pressure, latency spikes; reports typed finish "
                         "reasons + per-step health (runtime/lifecycle)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="chaos schedule seed (same seed => same faults)")
    ap.add_argument("--abft", action="store_true",
                    help="checksummed serving (implies --continuous): "
                         "pallas_mx GEMMs verify ABFT checksums at write-"
                         "back and the host logits copy is checksummed "
                         "against the device array; with --chaos, seeded "
                         "SDC bit flips drive the detect/correct path "
                         "(kernels/abft, runtime/batcher)")
    ap.add_argument("--fault-rate", type=float, default=0.1,
                    help="per-step fault probability under --chaos")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decoding (implies --paged): draft K "
                         "tokens per slot per step and verify all K+1 "
                         "positions in one widened flash-decode launch; "
                         "greedy-exact, so the emitted stream is bitwise "
                         "identical to plain decode (runtime/speculative)")
    ap.add_argument("--draft", default="ngram",
                    help="drafter under --speculate: 'ngram' (self-"
                         "speculative prompt lookup, no extra model) or an "
                         "arch id for a small draft model sharing the "
                         "target's token space")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="batch prefill: push the prompt through the cache "
                         "this many tokens per launch instead of one decode "
                         "step per token (0 = token stepping)")
    ap.add_argument("--disagg", type=int, default=0, metavar="N",
                    help="disaggregated serving: N prefill workers hand "
                         "finished requests to the decode pool by shipping "
                         "the page table (runtime/disagg.py)")
    ap.add_argument("--disagg-migrate", action="store_true",
                    help="disjoint prefill/decode pools: handoff migrates "
                         "pages (copy + re-mount) instead of the shared-"
                         "pool metadata handoff")
    args = ap.parse_args(argv)
    if args.disagg_migrate and not args.disagg:
        ap.error("--disagg-migrate requires --disagg N")
    if args.chaos:
        args.continuous = True  # chaos lives in the batcher's step loop
    if args.abft:
        args.continuous = True  # the ABFT guard lives in the batcher's step
        if args.disagg:
            ap.error("--abft rides the continuous batcher's step loop; "
                     "combine with --continuous/--paged, not --disagg")
    if args.prefix_cache:
        args.paged = True  # the prefix index lives on the page pool
    if args.disagg:
        args.paged = True  # workers prefill into the page pool
    if args.speculate:
        args.paged = True  # drafts land in (and roll back over) KV pages
        if args.disagg:
            ap.error("--speculate is a decode-loop feature; combine with "
                     "--continuous/--paged, not --disagg")
    if args.kv_cache != "f32" and not args.paged:
        ap.error("--kv-cache int8 requires --paged (the quantized cache "
                 "lives in the page pool)")
    return args


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg, model, rules, params = load_model(args)
    if cfg.model_kind == "encdec":
        print("enc-dec serving: decoder decode against a fixed encoder memory")
    max_len = args.max_len or (args.prompt_len + args.gen)
    # over several devices the TP projections ride the ring collective
    # matmuls, and every Pallas call is partitioned explicitly
    tp = (collective_policy(rules.mesh) if rules.mesh.shape["model"] > 1
          else contextlib.nullcontext())

    with use_rules(rules), tp:
        B = args.batch
        rng = np.random.default_rng(0)

        if args.disagg and cfg.model_kind != "encdec":
            return _run_disagg(model, cfg, params, args)
        if (args.continuous or args.paged) and cfg.model_kind != "encdec":
            return _run_continuous(model, cfg, params, args, rules)

        prompt = rng.integers(0, cfg.vocab, (B, args.prompt_len)).astype(np.int32)
        cache = place(rules, model.make_cache(B, max_len, mode="init"),
                      model.make_cache(B, max_len, mode="axes"))
        serve = make_serve_step(model, cfg)
        if cfg.model_kind == "encdec":
            frames = jnp.asarray(
                rng.standard_normal((B, 32, cfg.frontend_dim)), jnp.float32
            ) * 0.1
            enc_out = model.encode(params, frames)
            step = jax.jit(lambda p, c, t, i: serve(p, c, t, i, enc_out))
        else:
            step = jax.jit(serve)

        chunk = args.prefill_chunk
        can_chunk = (chunk > 1 and cfg.model_kind != "encdec"
                     and model.supports_chunked_prefill())
        if chunk > 1 and not can_chunk:
            print(f"chunked prefill unsupported for {cfg.name}; "
                  "falling back to token stepping")

        t0 = time.time()
        if can_chunk:
            # batched prefill: each launch pushes a whole chunk through the
            # cache (the flash prefill path), so time-to-first-token is
            # O(prompt_len / chunk) launches instead of O(prompt_len)
            prefill = jax.jit(make_chunked_prefill_step(model, cfg))
            t = 0
            while t < args.prompt_len:
                c = min(chunk, args.prompt_len - t)
                logits, cache = prefill(params, cache, prompt[:, t : t + c], t)
                t += c
            ttft = time.time() - t0
            print(f"prefill: {args.prompt_len} tokens in chunks of {chunk}; "
                  f"TTFT {ttft * 1e3:.1f}ms")
        else:
            # token-stepping prefill keeps one code path for archs without
            # the chunked path (state blocks, shared blocks, prefix embeds)
            for t in range(args.prompt_len):
                logits, cache = step(params, cache, prompt[:, t : t + 1], t)
        out_tokens = []
        for t in range(args.prompt_len, args.prompt_len + args.gen):
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out_tokens.append(np.asarray(tok))
            logits, cache = step(params, cache, tok, t)
        wall = time.time() - t0

    gen = np.concatenate(out_tokens, axis=1)
    total_tokens = B * (args.prompt_len + args.gen)
    print(f"generated {gen.shape} tokens; "
          f"{total_tokens / wall:.1f} tok/s (batch {B}, {device_label()})")
    print("sample:", gen[0][:16].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
