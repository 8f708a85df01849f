"""End-to-end training launcher.

Runs a real training loop on whatever devices exist (CPU smoke configs in
this container; the same code path jits onto a TPU mesh at scale), with the
full substrate engaged: sharded data pipeline, AdamW, async checkpointing,
fault-tolerant restart loop, straggler detection, metrics CSV.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 20 --inject-failure 7
"""
from __future__ import annotations

import argparse
import csv
import sys
import time

import jax
import jax.numpy as jnp

from ..checkpoint.manager import CheckpointManager
from ..configs import ARCH_IDS, get_config
from ..data.pipeline import SyntheticLM
from ..launch.compile_cache import enable_compile_cache
from ..launch.mesh import make_mesh
from ..launch.steps import make_train_step
from ..models import build_model
from ..optim.adamw import AdamW
from ..optim.schedules import warmup_cosine
from ..parallel.sharding import make_rules, tree_shardings, use_rules
from ..runtime.fault import FaultInjector, TrainLoop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--inject-failure", type=int, default=None,
                    help="simulate a node failure at this step (tests recovery)")
    ap.add_argument("--mesh", default=None,
                    help="mesh shape, e.g. 2x2 (defaults to 1x<ndevices>)")
    ap.add_argument("--metrics-csv", default=None)
    ap.add_argument("--param-dtype", default="float32")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    ndev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        shape = (1, ndev)
    mesh = make_mesh(shape, ("data", "model"))
    rules = make_rules(mesh, profile=cfg.parallelism, fsdp=cfg.fsdp)
    dtype = jnp.dtype(args.param_dtype)

    model = build_model(cfg)
    opt = AdamW(lr=warmup_cosine(args.lr, max(args.steps // 10, 1), args.steps))
    print(f"arch={cfg.name} params≈{cfg.n_params()/1e6:.1f}M mesh={dict(mesh.shape)}")

    with use_rules(rules):
        params = model.init(jax.random.PRNGKey(0), dtype=dtype)
        opt_state = opt.init(params)
        pshard = tree_shardings(rules, model.abstract(dtype), model.axes())
        params = jax.tree.map(jax.device_put, params, pshard)

        step_fn = make_train_step(model, cfg, opt)
        jstep = jax.jit(step_fn, donate_argnums=(0, 1))

        data = SyntheticLM(cfg, seq_len=args.seq, global_batch=args.batch)
        ckpt = CheckpointManager(args.ckpt_dir)
        injector = FaultInjector(
            fail_at_steps=(args.inject_failure,) if args.inject_failure else ()
        )
        rows = []

        def on_metrics(step, metrics):
            m = {k: float(v) for k, v in metrics.items()}
            rows.append({"step": step, **m})
            if step % 5 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {m.get('loss', float('nan')):.4f} "
                      f"gnorm {m.get('grad_norm', float('nan')):.3f}", flush=True)

        loop = TrainLoop(
            train_step=jstep, ckpt=ckpt, checkpoint_every=args.ckpt_every,
            fault_injector=injector, on_metrics=on_metrics,
        )
        start = ckpt.latest_step() or 0
        if start:
            print(f"resuming from checkpoint step {start}")
            state = ckpt.restore({"params": params, "opt": opt_state, "step": 0})
            params, opt_state = state["params"], state["opt"]
        t0 = time.time()
        params, opt_state, hist = loop.run(
            params, opt_state, data, total_steps=args.steps, start_step=start
        )
        wall = time.time() - t0

    print(f"done: {hist['steps_run']} steps in {wall:.1f}s "
          f"({hist['restarts']} restarts, stragglers at {hist['stragglers']})")
    if rows:
        first, last = rows[0], rows[-1]
        print(f"loss {first['loss']:.4f} -> {last['loss']:.4f}")
    if args.metrics_csv and rows:
        with open(args.metrics_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
