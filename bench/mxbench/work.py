"""Operations and bytes the work needs, from the configuration's shapes.

Counts are of what the algorithm needs, not of what a kernel happens to do:
a share of a roofline built from them cannot pass 100% unless a kernel
does the work in less time than the chip's peaks allow.  Operands are float32
(4 bytes), as the configurations state."""
from __future__ import annotations

from typing import Dict, List, Tuple

F32 = 4


def shapes(c: dict):
    return (c["hidden_size"], c["num_hidden_layers"], c["intermediate_size"],
            c["vocab_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"])


def layer_params(c: dict) -> int:
    """Weights of one block that multiply activations (norms left out)."""
    d, _, ff, _, H, Hkv, hd = shapes(c)
    return d * (H + 2 * Hkv) * hd + H * hd * d + 3 * d * ff


def gemm(M: int, K: int, N: int, *, weights: int = 1, bias: bool = False,
         residual: bool = False) -> Tuple[float, float]:
    """(flops, bytes) of an (M, K) x (K, N) product over ``weights``
    weight matrices that share the input (a fused gate and up projection
    has two), with the optional bias read and residual read."""
    flops = 2.0 * M * K * N * weights
    nbytes = F32 * (weights * K * N + M * K + M * N
                    + (N if bias else 0) + (M * N if residual else 0))
    return flops, nbytes


def decode_gemms(c: dict, M: int) -> List[Tuple[float, float]]:
    """Every fused GEMM one decode step launches at M rows: each block's
    q, k, v, output, gate-and-up and down projections, and the LM head."""
    d, L, ff, V, H, Hkv, hd = shapes(c)
    b = bool(c["attention_bias"])
    block = [gemm(M, d, H * hd, bias=b), gemm(M, d, Hkv * hd, bias=b),
             gemm(M, d, Hkv * hd, bias=b), gemm(M, H * hd, d, residual=True),
             gemm(M, d, ff, weights=2), gemm(M, ff, d, residual=True)]
    return block * L + [gemm(M, d, V)]


def flash_decode(c: dict, live_tokens: int, active: int
                 ) -> Tuple[float, float]:
    """(flops, bytes) of decode attention over every block for one step:
    the live K and V rows read once, the queries read and the outputs
    written for the active slots."""
    _, L, _, _, H, Hkv, hd = shapes(c)
    flops = 4.0 * live_tokens * H * hd * L
    nbytes = F32 * L * (2 * live_tokens * Hkv * hd + 2 * active * H * hd)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: compute or HBM, whichever
    bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def token_flops(c: dict, position: int, head: bool) -> float:
    """Model FLOPs of one token through the model at ``position`` (0-based):
    the block weights, attention over the position + 1 live rows, and the LM
    head only where the token's logits are used."""
    d, L, _, V, H, _, hd = shapes(c)
    f = 2.0 * layer_params(c) * L + 4.0 * L * H * hd * (position + 1)
    return f + (2.0 * d * V if head else 0.0)


def prompt_flops(c: dict, prompt_len: int) -> float:
    """A whole prompt: every position through the blocks, the head once
    (for the first answer token)."""
    d, L, _, V, H, _, hd = shapes(c)
    P = prompt_len
    return (2.0 * layer_params(c) * L * P
            + 4.0 * L * H * hd * P * (P + 1) / 2 + 2.0 * d * V)
