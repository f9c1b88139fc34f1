"""The plain reference's shared part: AdamW training over a network module.

The network itself (its parameters, forward pass and loss) is the module a
configuration names under ``"network"`` (``bench/networks/<name>.py``,
loaded by ``catalog.load_cell``). What is here serves every network: the
seed's JAX key, padding a batch to the step's rows, and the AdamW step as
the configuration's ``optimizer`` states it: global-norm clipping, bias
correction, decoupled weight decay, linear warm-up then cosine decay of the
learning rate. It imports nothing of the program; the check runs it under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["train_steps", "jax_key", "leaves", "pad_rows"]


def jax_key(seed: int):
    """A JAX key from the whole seed (``PRNGKey`` keeps only 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _lr(opt, t):
    warm = min(t / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((t - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * prog))


def pad_rows(a, rows):
    """``a`` with zero rows after it, ``rows`` in all, in ``a``'s dtype."""
    a = np.asarray(a)
    return np.concatenate([a, np.zeros((rows - len(a),) + a.shape[1:], a.dtype)])


@functools.lru_cache(maxsize=None)
def _grad_fn(net, cfg_json: str):
    """The jitted loss and gradient of a network and configuration, made
    once a process."""
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(lambda p, x, y, m, t: net.loss(p, x, y, m, cfg, t)))


def train_steps(net, params, batches, cfg, rows: int):
    """AdamW from ``params`` over ``batches`` [(x, y), ...] of real rows,
    with the loss of the network module ``net``.

    Each batch is padded with zero rows to ``rows``, so that one program
    serves every step; the padding is masked out of the loss, which also
    gets the step's number (1-based). Returns ``(losses, first_grad,
    params_after)``: the loss of each step, the first step's gradient as
    AdamW takes it (after clipping), and the parameters after the last step,
    as host float32 trees. The passes run at the caller's
    ``jax.default_matmul_precision``; AdamW is float32.
    """
    opt = cfg["optimizer"]
    grad_fn = _grad_fn(net, json.dumps(cfg, sort_keys=True))
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first = [], None
    for t, (x, y) in enumerate(batches, start=1):
        mask = np.zeros(rows, np.float32)
        mask[: len(x)] = 1.0
        value, g = grad_fn(p, pad_rows(x, rows), pad_rows(y, rows), mask, np.int32(t))
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


def leaves(tree) -> list[np.ndarray]:
    return [np.asarray(a, np.float32) for a in jax.tree_util.tree_leaves(tree)]
