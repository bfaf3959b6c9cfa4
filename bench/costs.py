"""Operations and bytes of each kernel call, from shapes.

Counts are what the algorithm needs, not what a kernel happens to do:
a packed weight's bytes are counted once per call however many row
tiles re-read it, and padding rows are not work.
"""
from __future__ import annotations

from typing import Tuple

BF16 = 2


def bitserial(m: int, k: int, n: int, n_bits: int) -> Tuple[float, float]:
    """(flops, bytes) of ``x (m, k) bf16 @ W (k, n)`` with W packed in
    ``n_bits`` magnitude planes and a sign plane, a float32 scale row,
    and a bf16 result."""
    flops = 2.0 * m * k * n
    weight = (n_bits + 1) * k * n / 8.0
    return flops, weight + 4.0 * n + BF16 * m * k + BF16 * m * n


def paged_attention(rows: int, n_heads: int, n_kv: int, head_dim: int) -> Tuple[float, float]:
    """(flops, bytes) of one layer's decode attention over ``rows`` cached
    rows in all (lanes summed): q.K and p.V per query head, and the bf16
    K and V rows of every kv head read once."""
    flops = 4.0 * n_heads * head_dim * rows
    return flops, 2.0 * BF16 * n_kv * head_dim * rows


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Least seconds the chip needs, and which roof bounds it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

