"""Runs one cell several times on the chips of this machine, each run a
process of its own, and prints what each reported.

    python bench/series.py --workload <cell> --seconds <s> --seeds 1,2,3 \\
        [--rates 1.5,2] [--control 1] [--trace 1] [--out runs.jsonl]

Every seed runs at every rate (the mix's own rate when none is given).
This is how the knee was swept (`--rates`), the precision control read
(`--control 1`: the control stands in the program's place and the run's
`correct` is the control's) and the sets of runs that set the bounds made
(PERF.md).  One line per run, `RUN {...}`, with the run's last line and the
end of its standard error; `--out` also appends those lines to a file."""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).with_name("run.py")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", default="0")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rates = [r for r in args.rates.split(",") if r] or [None]
    bad = 0
    for rate in rates:
        for seed in args.seeds.split(","):
            cmd = [sys.executable, str(RUN), "--workload", args.workload,
                   "--seed", seed, "--seconds", args.seconds,
                   "--trace", args.trace, "--control", args.control]
            if rate is not None:
                cmd += ["--rate", rate]
            t = time.perf_counter()
            p = subprocess.run(cmd, capture_output=True, text=True)
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except json.JSONDecodeError:
                result = None
            bad += p.returncode != 0 or result is None
            row = {"seed": int(seed), "rate": rate, "rc": p.returncode,
                   "wall_s": time.perf_counter() - t, "result": result,
                   "stderr": p.stderr[-1500:]}
            line = "RUN " + json.dumps(row)
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
