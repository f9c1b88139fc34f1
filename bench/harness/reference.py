"""Plain reference of the surrogate CNNs and of their AdamW training step.

Written from the configuration file alone, in float32 ``jax.numpy`` and
``jax.lax`` convolutions; the check runs it under
``jax.default_matmul_precision("highest")``.
It imports nothing of the program. It also makes the initial weights that
the program trains, so that both start from the same parameters and the
reference takes nothing the program made.

The network (the configuration's ``kind``):
  * encoder: ``depth`` 3^d convolutions of stride 2, SAME padding,
    ``base_channels * 2**i`` channels, each followed by leaky ReLU (0.01);
  * ``ptychonn``/``autophasenn`` decoder: ``depth`` 3^d transposed
    convolutions of stride 2 back to the input size, ``base_channels *
    2**(depth-2-i)`` channels and the output channels last, leaky ReLU
    between them;
  * ``cosmoflow`` head: flatten, dense to 128 with leaky ReLU, dense to the
    targets;
  * loss: the mean square error of each real row, averaged over real rows;
  * AdamW as the configuration's ``optimizer`` states: global-norm
    clipping, bias correction, decoupled weight decay, linear warm-up then
    cosine decay of the learning rate.

The parameter tree is the one the program's model reads:
``{"enc": [{"w", "b"}], "dec": [{"w", "b"}], "head": None | {"w1", "b1",
"w2", "b2"}}``, kernels laid out spatial dims, in, out.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_params", "forward", "loss", "train_steps", "jax_key", "last_layer", "leaves",
           "pad_rows"]

_SLOPE = 0.01
_HEAD = 128


def jax_key(seed: int):
    """A JAX key from the whole seed (``PRNGKey`` keeps only 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _rank(cfg) -> int:
    return len(cfg["input_shape"]) - 1


def _shapes(cfg) -> dict:
    """Leaf shapes of the parameter tree, from the configuration."""
    rank, ch, depth = _rank(cfg), cfg["base_channels"], cfg["depth"]
    k = (3,) * rank
    enc, c = [], cfg["input_shape"][-1]
    for i in range(depth):
        enc.append((k + (c, ch * 2**i), (ch * 2**i,)))
        c = ch * 2**i
    dec, head = [], None
    if cfg["kind"] in ("ptychonn", "autophasenn"):
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
    if cfg["kind"] in ("ptychonn", "autophasenn"):
        for i, p in enumerate(params["dec"]):
            h = _up(h, p["w"], rank) + p["b"]
            if i < len(params["dec"]) - 1:
                h = _leaky(h)
        return h
    hd = params["head"]
    z = _leaky(h.reshape(h.shape[0], -1) @ hd["w1"] + hd["b1"])
    return z @ hd["w2"] + hd["b2"]


def loss(params, x, y, mask, cfg):
    """Mean over the real rows (``mask`` 1) of each row's mean square error;
    0 for a step that gave this rank no rows."""
    pred = forward(params, x, cfg).astype(jnp.float32)
    err = jnp.square(pred - y.astype(jnp.float32))
    per_row = jnp.mean(err.reshape(err.shape[0], -1), axis=1)
    return jnp.sum(per_row * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _lr(opt, t):
    warm = min(t / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((t - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * prog))


def pad_rows(a, rows):
    """``a`` with zero rows after it, ``rows`` in all."""
    a = np.asarray(a, np.float32)
    return np.concatenate([a, np.zeros((rows - len(a),) + a.shape[1:], np.float32)])


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_json: str):
    """The jitted loss and gradient of a configuration, made once a process."""
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(lambda p, x, y, m: loss(p, x, y, m, cfg)))


def train_steps(params, batches, cfg, rows: int):
    """AdamW from ``params`` over ``batches`` [(x, y), ...] of real rows.

    Each batch is padded with zero rows to ``rows``, so that one program
    serves every step; the padding is masked out of the loss. Returns
    ``(losses, first_grad, params_after)``: the loss of each step, the first
    step's gradient as AdamW takes it (after clipping), and the parameters
    after the last step, as host float32 trees. The passes run at the
    caller's ``jax.default_matmul_precision``; AdamW is float32.
    """
    opt = cfg["optimizer"]
    grad_fn = _grad_fn(json.dumps(cfg, sort_keys=True))
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first = [], None
    for t, (x, y) in enumerate(batches, start=1):
        mask = np.zeros(rows, np.float32)
        mask[: len(x)] = 1.0
        value, g = grad_fn(p, pad_rows(x, rows), pad_rows(y, rows), mask)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
        g = jax.tree_util.tree_map(lambda a: a * scale, g)
        if first is None:
            first = jax.device_get(g)
        lr, b1, b2 = _lr(opt, t), opt["b1"], opt["b2"]
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        c1, c2 = 1 - b1**t, 1 - b2**t
        p = jax.tree_util.tree_map(
            lambda a, mm, vv: a - lr * ((mm / c1) / (jnp.sqrt(vv / c2) + opt["eps"])
                                        + opt["weight_decay"] * a), p, m, v)
        losses.append(float(value))
    return losses, first, jax.device_get(p)


def last_layer(cfg) -> np.ndarray:
    """Which of ``leaves``' leaves belong to the layer next to the loss: the
    last transposed convolution, or the head's last dense layer."""
    shapes = _shapes(cfg)
    tree = {"enc": [{"w": 0, "b": 0} for _ in shapes["enc"]],
            "dec": [{"w": 0, "b": 0} for _ in shapes["dec"]], "head": None}
    if shapes["head"] is None:
        tree["dec"][-1] = {"w": 1, "b": 1}
    else:
        tree["head"] = {"w1": 0, "b1": 0, "w2": 1, "b2": 1}
    return np.array(jax.tree_util.tree_leaves(tree), bool)


def leaves(tree) -> list[np.ndarray]:
    return [np.asarray(a, np.float32) for a in jax.tree_util.tree_leaves(tree)]
