"""Mamba-1 selective scan as a Pallas TPU kernel.

The recurrence  h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t ,  y_t = C_t.h_t + D u_t
is sequential in t, so the kernel tiles the *channel* dimension (DI) across
the parallel grid and keeps the [block_d, N] state h in VMEM scratch across
the (innermost, "arbitrary") sequence-block grid axis.  Within a sequence
block the timestep loop runs over VMEM-resident tiles:

  u/dt tiles [block_s, block_d], B/C tiles [block_s, N], h [block_d, N].

No [B, S, DI, N] tensor ever exists — the XLA associative-scan path
materializes exactly that (in log₂ S passes), which is why the SSM cells are
memory-bound at baseline (EXPERIMENTS.md §Perf, falcon-mamba hillclimb).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan_kernel"]

#: timesteps per aligned row tile; block_s is rounded to a multiple of it.
_ROWS = 16


def _kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
            h_ref, *, block_s: int):
    ib = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(ib == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)          # [bd, N]
    d_skip = d_ref[0].astype(jnp.float32)       # [bd]

    # Mosaic loads only sublane-aligned row tiles (16 rows covers bf16's
    # packed (16, 128) tiling), so each tile is loaded once and its
    # timesteps are indexed in registers.
    def tile(r, h):
        t0 = pl.multiple_of(r * _ROWS, _ROWS)
        rows = pl.ds(t0, _ROWS)
        u = u_ref[0, rows, :].astype(jnp.float32)    # [rows, bd]
        dt = dt_ref[0, rows, :].astype(jnp.float32)  # [rows, bd]
        bt = b_ref[0, rows, :].astype(jnp.float32)   # [rows, N]
        ct = c_ref[0, rows, :].astype(jnp.float32)   # [rows, N]
        for i in range(_ROWS):
            decay = jnp.exp(dt[i][:, None] * a)      # [bd, N]
            h = decay * h + (dt[i] * u[i])[:, None] * bt[i][None, :]
            y = (h * ct[i][None, :]).sum(axis=1) + d_skip * u[i]
            y_ref[0, t0 + i] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s // _ROWS, tile, h_ref[...])
    h_ref[...] = h

    @pl.when(ib == ns - 1)
    def _finish():
        hout_ref[0, ...] = h_ref[...]


def selective_scan_kernel(
    u, dt, a, b_ssm, c_ssm, d_skip, *, block_d: int = 256, block_s: int = 128,
    interpret: bool = False,
):
    """u, dt [B, S, DI]; a [DI, N]; b/c [B, S, N]; d_skip [DI].

    Returns (y [B, S, DI] f32, h_last [B, DI, N] f32).
    """
    bsz, s, di = u.shape
    n = a.shape[1]
    block_d = min(block_d, di)
    block_s = min(block_s, pl.cdiv(s, _ROWS) * _ROWS)
    assert di % block_d == 0, (di, block_d)
    assert block_s % _ROWS == 0, (block_s, _ROWS)
    pad_s = (-s) % block_s
    if pad_s:
        z = ((0, 0), (0, pad_s), (0, 0))
        u, dt = jnp.pad(u, z), jnp.pad(dt, z)
        b_ssm, c_ssm = jnp.pad(b_ssm, z), jnp.pad(c_ssm, z)
    sp = s + pad_s
    grid = (bsz, di // block_d, sp // block_s)

    kernel = functools.partial(_kernel, block_s=block_s)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda ib_, id_, is_: (ib_, is_, id_)),
            pl.BlockSpec((1, block_s, block_d), lambda ib_, id_, is_: (ib_, is_, id_)),
            pl.BlockSpec((block_d, n), lambda ib_, id_, is_: (id_, 0)),
            pl.BlockSpec((1, block_s, n), lambda ib_, id_, is_: (ib_, is_, 0)),
            pl.BlockSpec((1, block_s, n), lambda ib_, id_, is_: (ib_, is_, 0)),
            # 2-D so the block's layout matches XLA's tiling of the operand.
            pl.BlockSpec((1, block_d), lambda ib_, id_, is_: (0, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda ib_, id_, is_: (ib_, is_, id_)),
            pl.BlockSpec((1, block_d, n), lambda ib_, id_, is_: (ib_, id_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, sp, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(u, dt, a, b_ssm, c_ssm, d_skip.reshape(1, di))
    # dt=0 padding leaves h untouched (decay=1, input=0), so h_last is exact.
    return y[:, :s], h_last
