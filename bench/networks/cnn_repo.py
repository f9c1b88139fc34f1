"""The repository's rendering of the surrogate CNNs, as a plain reference.

A configuration names this file under ``"network"``. Written from the
configuration alone, in float32 ``jax.numpy`` and ``jax.lax`` convolutions;
it imports nothing of the program. It also makes the initial weights that
the program trains, so that both start from the same parameters.

The network (the configuration's ``kind``):
  * encoder: ``depth`` 3^d convolutions of stride 2, SAME padding,
    ``base_channels * 2**i`` channels, each followed by leaky ReLU (0.01);
  * ``ptychonn``/``autophasenn`` decoder: ``depth`` 3^d transposed
    convolutions of stride 2 back to the input size, ``base_channels *
    2**(depth-2-i)`` channels and the output channels last, leaky ReLU
    between them;
  * ``cosmoflow`` head: flatten, dense to 128 with leaky ReLU, dense to the
    targets;
  * loss: the mean square error of each real row, averaged over real rows.

The parameter tree is the one the program's model reads:
``{"enc": [{"w", "b"}], "dec": [{"w", "b"}], "head": None | {"w1", "b1",
"w2", "b2"}}``, kernels laid out spatial dims, in, out.

Model FLOPs (``forward_flops``): the multiply-adds of the convolutions and
dense layers (2 FLOPs each); elementwise work (bias, activation, loss) is
left out. A stride-2 convolution does its whole 3^d window at each output
position; a stride-2 transposed convolution does it at each input position.
XLA's cost analysis counts only the products that fall inside the input,
neither padding nor the zeros of a dilated input, so it reads a little less
at the borders (bench/tests/test_bench_flops.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_params", "forward", "loss", "last_layer", "forward_flops",
           "train_flops_per_sample"]

_SLOPE = 0.01
_HEAD = 128


def _rank(cfg) -> int:
    return len(cfg["input_shape"]) - 1


def _has_decoder(cfg) -> bool:
    return cfg["kind"] in ("ptychonn", "autophasenn")


def _shapes(cfg) -> dict:
    """Leaf shapes of the parameter tree, from the configuration."""
    rank, ch, depth = _rank(cfg), cfg["base_channels"], cfg["depth"]
    k = (3,) * rank
    enc, c = [], cfg["input_shape"][-1]
    for i in range(depth):
        enc.append((k + (c, ch * 2**i), (ch * 2**i,)))
        c = ch * 2**i
    dec, head = [], None
    if _has_decoder(cfg):
        for i in range(depth):
            out = ch * 2 ** (depth - 2 - i) if i < depth - 1 else cfg["output_shape"][-1]
            dec.append((k + (c, out), (out,)))
            c = out
    else:
        flat = c * (cfg["input_shape"][0] // 2**depth) ** rank
        head = {"w1": (flat, _HEAD), "b1": (_HEAD,),
                "w2": (_HEAD, cfg["output_shape"][0]), "b2": (cfg["output_shape"][0],)}
    return {"enc": enc, "dec": dec, "head": head}


def init_params(key, cfg) -> dict:
    """Weights normal over sqrt(fan in), biases zero, one key per leaf."""
    shapes = _shapes(cfg)
    keys = iter(jax.random.split(key, len(shapes["enc"]) + len(shapes["dec"]) + 2))

    def dense(shape):
        fan_in = math.prod(shape[:-1])
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

    params = {
        "enc": [{"w": dense(w), "b": jnp.zeros(b, jnp.float32)} for w, b in shapes["enc"]],
        "dec": [{"w": dense(w), "b": jnp.zeros(b, jnp.float32)} for w, b in shapes["dec"]],
        "head": None,
    }
    if shapes["head"] is not None:
        h = shapes["head"]
        params["head"] = {"w1": dense(h["w1"]), "b1": jnp.zeros(h["b1"], jnp.float32),
                          "w2": dense(h["w2"]), "b2": jnp.zeros(h["b2"], jnp.float32)}
    return params


def _dims(rank: int):
    spatial = "DHW"[3 - rank:]
    return (f"N{spatial}C", f"{spatial}IO", f"N{spatial}C")


def _down(x, w, rank):
    """Stride-2 convolution, SAME: output ceil(n/2); the odd pixel of
    padding goes after the input."""
    pads = []
    for n in x.shape[1:1 + rank]:
        total = max((-(-n // 2) - 1) * 2 + 3 - n, 0)
        pads.append((total // 2, total - total // 2))
    return jax.lax.conv_general_dilated(x, w, (2,) * rank, pads,
                                        dimension_numbers=_dims(rank))


def _up(x, w, rank):
    """Stride-2 transposed convolution, SAME: output 2n. Written as the
    convolution of the input dilated by 2 (a zero between neighbours) with
    the kernel as stored, padded 2 before and 1 after."""
    return jax.lax.conv_general_dilated(x, w, (1,) * rank, [(2, 1)] * rank,
                                        lhs_dilation=(2,) * rank,
                                        dimension_numbers=_dims(rank))


def _leaky(x):
    return jnp.where(x >= 0, x, _SLOPE * x)


def forward(params, x, cfg):
    rank = _rank(cfg)
    h = x
    for p in params["enc"]:
        h = _leaky(_down(h, p["w"], rank) + p["b"])
    if _has_decoder(cfg):
        for i, p in enumerate(params["dec"]):
            h = _up(h, p["w"], rank) + p["b"]
            if i < len(params["dec"]) - 1:
                h = _leaky(h)
        return h
    hd = params["head"]
    z = _leaky(h.reshape(h.shape[0], -1) @ hd["w1"] + hd["b1"])
    return z @ hd["w2"] + hd["b2"]


def loss(params, x, y, mask, cfg, step):
    """Mean over the real rows (``mask`` 1) of each row's mean square error;
    0 for a step that gave this rank no rows. ``step`` (1-based) is unused:
    this network draws nothing at random."""
    del step
    pred = forward(params, x, cfg).astype(jnp.float32)
    err = jnp.square(pred - y.astype(jnp.float32))
    per_row = jnp.mean(err.reshape(err.shape[0], -1), axis=1)
    return jnp.sum(per_row * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def last_layer(cfg) -> np.ndarray:
    """Which leaves of the parameter tree, in ``jax.tree_util.tree_leaves``
    order, belong to the layer next to the loss: the last transposed
    convolution, or the head's last dense layer."""
    shapes = _shapes(cfg)
    tree = {"enc": [{"w": 0, "b": 0} for _ in shapes["enc"]],
            "dec": [{"w": 0, "b": 0} for _ in shapes["dec"]], "head": None}
    if shapes["head"] is None:
        tree["dec"][-1] = {"w": 1, "b": 1}
    else:
        tree["head"] = {"w1": 0, "b1": 0, "w2": 1, "b2": 1}
    return np.array(jax.tree_util.tree_leaves(tree), bool)


def forward_flops(cfg) -> int:
    rank = _rank(cfg)
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
    if _has_decoder(cfg):
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
    """Model FLOPs of one training sample: forward, times 3 for forward plus
    backward."""
    return 3 * forward_flops(cfg)
