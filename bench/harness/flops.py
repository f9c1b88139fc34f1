"""Model FLOPs of one training sample, from the configuration's shapes.

Counted: the multiply-adds of the convolutions and dense layers (2 FLOPs
each), forward, times 3 for forward plus backward. Elementwise work (bias,
activation, loss) is left out. A stride-2 convolution does its whole 3^d
window at each output position; a stride-2 transposed convolution does it
at each input position. XLA's cost analysis counts only the products that
fall inside the input, neither padding nor the zeros of a dilated input, so
it reads a little less at the borders (bench/tests/test_bench_flops.py).
"""
from __future__ import annotations

import math

__all__ = ["forward_flops", "train_flops_per_sample"]

_HEAD = 128


def forward_flops(cfg) -> int:
    rank = len(cfg["input_shape"]) - 1
    window = 3**rank
    size = cfg["input_shape"][0]
    c = cfg["input_shape"][-1]
    ch, depth = cfg["base_channels"], cfg["depth"]
    total = 0
    for i in range(depth):
        size = -(-size // 2)
        out = ch * 2**i
        total += 2 * size**rank * window * c * out
        c = out
    if cfg["kind"] in ("ptychonn", "autophasenn"):
        for i in range(depth):
            out = ch * 2 ** (depth - 2 - i) if i < depth - 1 else cfg["output_shape"][-1]
            total += 2 * size**rank * window * c * out
            size *= 2
            c = out
    else:
        flat = c * size**rank
        total += 2 * flat * _HEAD + 2 * _HEAD * math.prod(cfg["output_shape"])
    return total


def train_flops_per_sample(cfg) -> int:
    return 3 * forward_flops(cfg)
