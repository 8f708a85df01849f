"""VMEM accounting for the Pallas TPU kernels, and the compiler parameters
built from it.

Mosaic refuses a kernel whose buffers exceed its scoped VMEM limit, 16 MiB
on TPU v5e unless the kernel asks for more ("Scoped allocation with size
60.00M and limit 16.00M").  What it allocates is exact and simple: every
pipelined block (inputs and outputs) twice, since the next block's DMA runs
while the current one is consumed; every scratch buffer once; each buffer
padded to whole (8, 128) tiles.  The values a kernel body materializes
(a dot's f32 result, f32 copies of narrow operands) come on top.

`gemm_vmem_bytes` is that count for the MX GEMM kernels.  The tile planner
(core/tiling.py) budgets plans with it and the kernels derive their
``vmem_limit_bytes`` from it, so every plan the planner returns is one the
compiler accepts.
"""
from __future__ import annotations

from typing import Sequence

from jax.experimental.pallas import tpu as pltpu

MiB = 1024 * 1024
# Physical VMEM of one TPU v5e TensorCore; a kernel's limit stays below it.
VMEM_CAPACITY = 128 * MiB
_HEADROOM = 4 * MiB  # Mosaic's own internal scratch beside the buffers


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def buffer_bytes(rows: int, cols: int, itemsize: float) -> int:
    """Bytes one (rows, cols) VMEM buffer occupies, padded to (8, 128)
    tiles.  ``itemsize`` may be fractional only through the caller's own
    arithmetic; it is rounded up here."""
    return int(_round_up(rows, 8) * _round_up(cols, 128) * itemsize + 0.5)


def gemm_vmem_bytes(
    bm: int, bn: int, bk: int, *,
    a_bytes: int, b_bytes: int, out_bytes: int,
    gated: bool = False, b_sparse: bool = False,
    a_scale: bool = False, b_scale: bool = False, bias: bool = False,
    residual_bytes: int = 0,
) -> int:
    """VMEM one (bm, bn, bk) MX GEMM launch claims: double-buffered A, B
    (and gate B), output and epilogue-operand blocks; the f32 accumulator
    (two when gated); and the body's f32 dot results, plus f32 copies of
    the operands when they are narrow or of mixed dtypes (`dot_f32`).  A
    2:4 block's in-VMEM expansion is left to the limit's headroom."""
    n_b = 2 if gated else 1
    kb = bk // 2 if b_sparse else bk  # a 2:4 payload stages half the rows
    piped = buffer_bytes(bm, bk, a_bytes) + n_b * buffer_bytes(kb, bn, b_bytes)
    piped += buffer_bytes(bm, bn, out_bytes)
    if b_sparse:
        piped += n_b * buffer_bytes(max(bk // 8, 1), bn, 1)
    if a_scale:
        piped += buffer_bytes(bm, 1, 4)
    piped += (n_b * b_scale + bias) * buffer_bytes(1, bn, 4)
    if residual_bytes:
        piped += buffer_bytes(bm, bn, residual_bytes)
    acc = n_b * buffer_bytes(bm, bn, 4)
    temps = n_b * buffer_bytes(bm, bn, 4)
    if a_bytes != b_bytes or a_bytes < 2:
        temps += buffer_bytes(bm, bk, 4) + n_b * buffer_bytes(bk, bn, 4)
    return 2 * piped + acc + temps


def paged_attention_vmem_bytes(
    rows: int, ps: int, lanes: int, ppb: int, *,
    kv_bytes: int, q_bytes: int, scale_rows: int = 0,
) -> int:
    """VMEM one paged-attention launch claims (`kernels/mx_flash_decode`)
    with ``rows`` query rows a slot, pages of ``ps`` pool rows ``lanes``
    wide and ``ppb`` pages a block: the double-buffered page, query, output,
    label (and ``scale_rows`` scale) blocks, the (m, l, acc) scratch, and
    the body's f32 values — each page cast, the block's K and V, the
    scores, probabilities and mask."""
    blk = ppb * ps
    piped = 2 * ppb * buffer_bytes(ps, lanes, kv_bytes)
    piped += 2 * buffer_bytes(rows, lanes, q_bytes) + buffer_bytes(rows, blk, 4)
    if scale_rows:
        piped += buffer_bytes(scale_rows, blk, 4)
    scratch = 2 * buffer_bytes(rows, 1, 4) + buffer_bytes(rows, lanes, 4)
    temps = 4 * buffer_bytes(blk, lanes, 4) + 3 * buffer_bytes(rows, blk, 4)
    return 2 * piped + scratch + temps


def compiler_params(dimension_semantics: Sequence[str],
                    vmem_bytes: int = 0) -> pltpu.CompilerParams:
    """Mosaic parameters for a kernel whose footprint is ``vmem_bytes``:
    the scoped limit is raised to cover it (with headroom for Mosaic's own
    scratch) whenever the 16 MiB default would not."""
    limit = None
    need = vmem_bytes + vmem_bytes // 4 + _HEADROOM
    if need > 16 * MiB:
        limit = min(need, VMEM_CAPACITY - 8 * MiB)
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=limit,
    )
