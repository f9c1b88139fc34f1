"""Jit'd wrappers around the Pallas kernels.

The kernels lower natively for the TPU.  ``interpret=True`` runs the exact
kernel body with the exact BlockSpec tiling in the Pallas interpreter on
any backend — the correctness contract is identical, only the speed
differs — and is only ever taken when the caller asks for it (the oracle
tests do), never as a silent fallback for a missing chip.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import selective_scan as _ss

__all__ = ["flash_attention", "selective_scan", "rms_norm"]


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("block_d", "block_s", "interpret"))
def selective_scan(u, dt, a, b_ssm, c_ssm, d_skip, *, h0=None,
                   block_d: int = 256, block_s: int = 128,
                   interpret: bool = False):
    if h0 is not None:
        raise NotImplementedError(
            "kernel path starts from h0=0; decode uses the recurrent step"
        )
    return _ss.selective_scan_kernel(
        u, dt, a, b_ssm, c_ssm, d_skip,
        block_d=block_d, block_s=block_s, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rms_norm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
             interpret: bool = False):
    return _rn.rms_norm_kernel(
        x, scale, eps=eps, block_rows=block_rows, interpret=interpret
    )
