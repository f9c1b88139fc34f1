"""Flash attention as a Pallas TPU kernel.

Tiling (VMEM-resident per grid step):
  q tile  [block_q, head_dim]     — revisited across the kv grid dim
  k tile  [block_k, head_dim]
  v tile  [block_k, head_dim]
  acc/m/l scratch persist across the kv dim (innermost grid axis), so the
  online-softmax state never leaves VMEM — that is the whole point vs the
  blockwise-XLA path, whose per-block score tensors round-trip HBM at every
  fusion boundary (measured in EXPERIMENTS.md §Perf).

Grid: (batch*q_heads, Sq/block_q, Sk/block_k) with the kv axis innermost
("arbitrary" semantics — the output tile is revisited).  GQA is handled in
the index maps: q head ``h`` reads kv head ``h // (H // K)``; no KV
replication in HBM.

MXU alignment: block_q/block_k default 128, head_dim padded to a multiple of
128 by the wrapper in ops.py when needed.  Causal and sliding-window masks
are applied with iota position math inside the tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention"]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, seq_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # [bq, bk]

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = kpos < seq_k
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= qpos - kpos < window
    s = jnp.where(ok, s, _NEG_INF)

    m_prev = m_ref[...]                                # [bq]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q, k, v, *, causal: bool = True, window: int = 0,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
):
    """q [B, H, Sq, hd]; k/v [B, K, Sk, hd] with K | H.  Returns [B, H, Sq, hd]."""
    b, h, sq, hd = q.shape
    kh, sk = k.shape[1], k.shape[2]
    assert h % kh == 0
    g = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k

    qf = q.reshape(b * h, sq_p, hd)
    kf = k.reshape(b * kh, sk_p, hd)
    vf = v.reshape(b * kh, sk_p, hd)
    grid = (b * h, sq_p // block_q, sk_p // block_k)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_k=sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            # GQA: q head index bh = b*H + h maps to kv row b*K + h//g,
            # which is exactly bh // g since H = K*g.
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qf, kf, vf)
    return out.reshape(b, h, sq_p, hd)[:, :, :sq]


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: bool = False, block_q: int = 128,
                    block_k: int = 128):
    """Public entry — see ops.py for the jit'd dispatching wrapper."""
    # kv-head grouping requires q heads grouped contiguously per kv head,
    # which [B, H, S, hd] already satisfies (h // g maps to the kv head).
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
