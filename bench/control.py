"""Runs one cell once with the precision control in the program's place, as
`bench/run.py --control 1` does, but takes the control's eager product of
the kept rows with the LM head in blocks of vocabulary columns.

    python bench/control.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The numbers are bench/run.py's: each column of the product is the same
contraction, in a block or not.  At `deepseek-67b-tp4`'s size the `high`
control splits the whole (8192, 102400) head into two bfloat16 halves beside
the reference's replicated embedding and head, which asks a v5e for more
memory than it has left; a block of `HEAD_COLUMNS` columns fits."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402  (its import starts the set-up clock)

HEAD_COLUMNS = 12800
ROWS_BY_HEAD = "pd,dv->pv"   # the check's eager read of the kept rows


def blocked(dot, columns: int = HEAD_COLUMNS):
    """``dot`` with the rows-by-head product taken ``columns`` at a time."""
    import jax.numpy as jnp

    def dot_in_blocks(eq, a, b):
        if eq != ROWS_BY_HEAD:
            return dot(eq, a, b)
        return jnp.concatenate([dot(eq, a, b[:, i:i + columns])
                                for i in range(0, b.shape[1], columns)],
                               axis=1)

    return dot_in_blocks


def main(argv=None, columns: int = HEAD_COLUMNS, **kw) -> dict:
    """bench/run.py's run with ``control``; ``kw`` as run.main takes it."""
    plain = run.reference_module

    def reference_module(cfg):
        ref = plain(cfg)    # a fresh module object each call
        ref.DOTS = dict(ref.DOTS, **{name: blocked(ref.DOTS[name], columns)
                                     for name in ("high", "bf16")})
        return ref

    run.reference_module = reference_module
    try:
        return run.main(argv, control=True, **kw)
    finally:
        run.reference_module = plain


if __name__ == "__main__":
    try:
        main()
    except run.Refused as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        sys.exit(3)
