"""Compiles a cell's serving programs for a described TPU v5e, without the
chip, and prints what each would hold in device memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload qwen2-0.5b.chat

It compiles the decode step at every page-table width and the chunked
prefill at every chunk length the cell's mix can reach, as the batcher calls
them, through the compiled Pallas kernels (not the interpreter), on one chip
or on a (1, 4) mesh of a described v5e:2x2 under the ring collective policy,
as the cell asks.  The
compiler refuses here what it would refuse on the chip: a tile it cannot
lay out, a kernel that asks for too much fast memory, a program that does
not fit the device."""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import run
    from mxbench import check
    from mxbench import weights as W
    from repro.core import ops
    from repro.core.hlo_census import kernel_launches
    from repro.models import build_model
    from repro.parallel.sharding import (
        collective_policy, make_rules, tree_shardings, use_rules)

    jax.config.update("jax_enable_compilation_cache", False)
    spec = run.load_cell(args.workload)
    cfg, chips = spec["cfg"], spec["cell"]["chips"]
    widths, tails = run.warm_shapes(cfg, spec["mix"], args.seconds)
    arch = run.arch_config(cfg)
    model = build_model(arch)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    rules = make_rules(mesh, profile=arch.parallelism)
    B, ps = cfg["slots"], cfg["page_size"]
    width = -(-cfg["max_len"] // ps)
    pages = cfg["pool_pages"] + 1  # the pool adds the dump page

    def sds(shape, dtype, sharding=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding or
                                    NamedSharding(mesh, P()))

    tp = collective_policy(mesh) if chips > 1 \
        else contextlib.nullcontext()
    pol = ops.MXPolicy(backend="pallas_mx", interpret=False)
    with use_rules(rules), tp, ops.use_policy(pol):
        abstract = model.abstract()
        params = jax.tree.map(lambda a, s: sds(a.shape, a.dtype, s), abstract,
                              tree_shardings(rules, abstract, model.axes()))
        cache_abs = model.make_paged_cache(pages, ps, mode="abstract",
                                           dtype=jnp.float32)
        cache = jax.tree.map(lambda a, s: sds(a.shape, a.dtype, s), cache_abs,
                             tree_shardings(rules, cache_abs,
                                            model.make_paged_cache(
                                                pages, ps, mode="axes")))
        i32 = jnp.int32
        programs = {
            f"decode_w{w}": (model.decode_step_paged,
                             (params, sds((B, 1), i32), cache, sds((B,), i32),
                              sds((B, w), i32), sds((B,), i32)))
            for w in widths}
        programs.update({
            f"prefill_c{c}": (model.prefill_step_paged,
                              (params, sds((1, c), i32), cache,
                               sds((1,), i32), sds((1, width), i32)))
            for c in tails})
        # the weights, drawn on the device in their shardings
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        programs["weights"] = (
            W.maker(run.reference_module(cfg).layout(cfg), jax.tree.map(
                lambda a: a.sharding, params)), (key,))
        # one reference block after the window, at the check's sizes
        ref = run.reference_module(cfg)
        whole = NamedSharding(Mesh(np.array(topo.devices[:chips]), ("s",)),
                              P())
        S = -(-check.SAMPLE // chips) * chips
        T = cfg["max_len"]
        layer = {p: sds(sh[1:], jnp.float32, whole) for p, (sh, _, _)
                 in ref.layout(cfg).items() if p[0] == "seg0"}
        programs["reference_layer"] = (
            check.layer_step(ref, cfg, ref.DOTS["highest"], S // chips, T),
            (W._nest(layer), sds((S, T, cfg["hidden_size"]), jnp.float32,
                                 NamedSharding(whole.mesh, P("s")))))
        out = {}
        for name, (fn, fargs) in programs.items():
            try:
                compiled = jax.jit(fn).lower(*fargs).compile()
            except Exception as e:  # report every shape the chip refuses
                out[name] = {"refused": str(e).splitlines()[0][:400]}
                print(f"{name}: REFUSED {out[name]['refused']}", flush=True)
                continue
            mem = compiled.memory_analysis()
            out[name] = {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "kernels": dict(kernel_launches(compiled.as_text())),
            }
            print(f"{name}: {json.dumps(out[name])}", flush=True)
    print(json.dumps({"workload": args.workload, "chips": chips,
                      "per_chip": out}))
    return 1 if any("refused" in v for v in out.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
