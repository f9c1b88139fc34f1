"""Sharding rules: logical param/activation layouts -> NamedSharding.

Strategy (DESIGN.md §5):
  * mesh axes ``(pod, data, model)`` (multi-pod) or ``(data, model)``.
  * Params are FSDP-sharded over ``data`` on one dim and tensor-parallel over
    ``model`` on another; replicated over ``pod`` (pure DP across pods keeps
    the slow inter-pod links off the layer critical path; gradient all-reduce
    over pods happens once per step and can be compressed).
  * Rules are *candidate lists*: the first PartitionSpec whose every mesh-axis
    assignment divides the corresponding dim is used, so architectures with
    awkward head/vocab counts (Hymba's 25 heads, Whisper's 51865 vocab)
    degrade gracefully to partial sharding instead of failing to compile.

The same rule engine shards the optimizer state (same layout as the param)
and the decode caches.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "choose_spec",
    "param_sharding",
    "batch_sharding",
    "cache_sharding",
    "constrain",
    "constrain_batch",
]

# fsdp dims shard over every data-parallel axis present (pod included:
# ZeRO-3 across pods halves param/opt memory on the 512-chip mesh at the
# cost of cross-pod param all-gathers — gradient compression targets those).
FSDP = ("pod", "data")
TP = "model"


def _axes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _fits(shape, spec, sizes) -> bool:
    for dim, assignment in zip(shape, spec):
        if assignment is None:
            continue
        names = assignment if isinstance(assignment, tuple) else (assignment,)
        total = int(np.prod([sizes[n] for n in names]))
        if dim % total != 0:
            return False
    return True


def choose_spec(shape, candidates, mesh: Mesh) -> P:
    """First candidate spec that divides ``shape`` on this mesh (else replicate).

    Axis names absent from the mesh are dropped from each assignment (so the
    same rules serve the single-pod and multi-pod meshes), and within a
    combined assignment, axes that stop dividing the dim are dropped
    greedily.
    """
    sizes = _axes(mesh)
    for spec in candidates:
        spec = tuple(spec)[: len(shape)]
        cleaned = []
        for dim, assignment in zip(shape, spec + (None,) * (len(shape) - len(spec))):
            if assignment is None:
                cleaned.append(None)
                continue
            names = assignment if isinstance(assignment, tuple) else (assignment,)
            keep, total = [], 1
            for n in names:
                if n in sizes and dim % (total * sizes[n]) == 0:
                    keep.append(n)
                    total *= sizes[n]
            cleaned.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
        spec = P(*cleaned)
        if _fits(shape, spec, sizes):
            return spec
    return P(*([None] * len(shape)))


# Per-leaf candidate specs, keyed by regex on the pytree path, written for
# the UNSTACKED tensor — the leading layer axis (None) is prepended for
# stacked leaves.  Earlier entries are preferred; axes that do not exist on
# the mesh or do not divide the dim are dropped per-entry.
_RULES: list[tuple[str, list[tuple]]] = [
    # embeddings / output head (unembed first: 'embed$' also matches it).
    # Single-axis sharding: vocab over TP only.  Sharding the d dim over
    # 'data' as well forces the token-gather's partial-sum all-reduce to
    # produce *batch-replicated* activations (measured: a [B_global, S, d/16]
    # f32 all-reduce per step) — see EXPERIMENTS.md §Perf iteration g3.
    (r"unembed$", [(None, TP), (FSDP, None), ()]),
    (r"embed$", [(TP, None), (None, FSDP), ()]),
    (r"mm_proj$", [(FSDP, TP), ()]),
    # attention
    (r"(wq|wk|wv)$", [(FSDP, TP, None), (TP, None, None), (FSDP,), ()]),
    (r"wo$", [(TP, None, FSDP), (None, None, FSDP), ()]),
    (r"(bq|bk|bv)$", [(TP, None), ()]),
    # dense / shared-expert MLPs
    (r"(wi_gate|wi_up|ws_gate|ws_up|wi)$", [(FSDP, TP), (None, TP), ()]),
    (r"(wo_mlp|ws_down|wo)$", [(TP, FSDP), (TP, None), ()]),
    (r"bi$", [(TP,), ()]),
    (r"bo$", [()]),
    # MoE experts: expert-parallel over model axis, FSDP over d.
    (r"router$", [(FSDP, None), ()]),
    (r"we_(gate|up)$", [(TP, FSDP, None), (TP, None, None), ()]),
    (r"we_down$", [(TP, None, FSDP), (TP, None, None), ()]),
    # Mamba / SSM
    (r"in_proj$", [(FSDP, TP), (None, TP), ()]),
    (r"conv_w$", [(None, TP), ()]),
    (r"(conv_b|dt_bias|d_skip)$", [(TP,), ()]),
    (r"x_proj$", [(TP, None), ()]),
    (r"dt_proj$", [(None, TP), ()]),
    (r"a_log$", [(TP, None), ()]),
    (r"out_proj$", [(TP, FSDP), (TP, None), ()]),
    # norms and everything else: replicated
    (r"(ln|norm|scale|bias)", [()]),
]

# Leaves that are NOT layer-stacked (no leading L axis to skip).
_UNSTACKED = re.compile(r"(embed|unembed|mm_proj|final|enc_final|dec_final)")


def _spec_for(path: str, shape, mesh: Mesh) -> P:
    stacked = _UNSTACKED.search(path) is None
    for pat, candidates in _RULES:
        if re.search(pat, path):
            if stacked:
                # stacked leaves carry a leading [num_layers] axis
                cands = [(None,) + tuple(c) for c in candidates]
            else:
                cands = list(candidates)
            return choose_spec(shape, cands, mesh)
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def constrain(x, *logical_spec):
    """Activation sharding constraint that degrades gracefully.

    ``logical_spec`` names mesh axes per dim (tuple entries = combined axes).
    Axes absent from the current abstract mesh are dropped; axes whose size
    does not divide the dim are dropped; outside any mesh this is a no-op.
    Keeping activations pinned to the batch axes is what makes the GSPMD
    partitioner all-gather *weights* (FSDP) instead of activations — without
    these constraints the 0.5B-vocab CE graph all-gathered the whole global
    batch per device (EXPERIMENTS.md §Perf, iteration 0).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        # `with mesh:` (launch/dryrun.py) sets only the thread-resources
        # context, not the abstract mesh; bare PartitionSpecs resolve there.
        from jax._src import mesh as mesh_lib

        mesh = mesh_lib.thread_resources.env.physical_mesh
        if mesh.empty:
            return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    spec = []
    for dim, ax in zip(x.shape, logical_spec):
        if ax is None:
            spec.append(None)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        keep, total = [], 1
        for n in names:
            if n in sizes and dim % (total * sizes[n]) == 0:
                keep.append(n)
                total *= sizes[n]
        spec.append(tuple(keep) if keep else None)
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_batch(x):
    """Pin dim0 to the data-parallel axes, replicate the rest."""
    return constrain(x, ("pod", "data"), *([None] * (x.ndim - 1)))


def param_sharding(params, mesh: Mesh):
    """NamedSharding pytree for a param (or optimizer-state) pytree."""

    def leaf(path, x):
        spec = _spec_for(_path_str(path), x.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params)


def batch_sharding(batch, mesh: Mesh):
    """Shard the leading (batch) dim over every data-parallel axis that fits."""
    dp_axes = tuple(n for n in mesh.axis_names if n in ("pod", "data"))
    sizes = _axes(mesh)

    def leaf(x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        usable = []
        total = 1
        for n in dp_axes:
            if x.shape[0] % (total * sizes[n]) == 0:
                usable.append(n)
                total *= sizes[n]
        spec = (tuple(usable),) + (None,) * (x.ndim - 1) if usable else (None,) * x.ndim
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(leaf, batch)


def cache_sharding(cache, mesh: Mesh, *, kv_heads: int):
    """Decode-cache layout: [L, B, K, S, hd] — batch over data axes, heads
    over 'model' when divisible, else the sequence axis over 'model'
    (flash-decoding partial softmax; DESIGN.md §4)."""
    sizes = _axes(mesh)
    tp = sizes.get(TP, 1)
    dp_axes = tuple(n for n in mesh.axis_names if n in ("pod", "data"))

    def batch_axes(b):
        usable, total = [], 1
        for n in dp_axes:
            if b % (total * sizes[n]) == 0:
                usable.append(n)
                total *= sizes[n]
        return tuple(usable) if usable else None

    def leaf(path, x):
        name = _path_str(path)
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        if name in ("k", "v", "ck", "cv"):
            L_, b, k, s, hd = x.shape
            if k % tp == 0:
                spec = P(None, batch_axes(b), TP, None, None)
            elif s % tp == 0:
                spec = P(None, batch_axes(b), None, TP, None)
            else:
                spec = P(None, batch_axes(b), None, None, None)
            return NamedSharding(mesh, spec)
        if name in ("k_scale", "v_scale"):
            L_, b, k, s = x.shape
            if k % tp == 0:
                spec = P(None, batch_axes(b), TP, None)
            elif s % tp == 0:
                spec = P(None, batch_axes(b), None, TP)
            else:
                spec = P(None, batch_axes(b), None, None)
            return NamedSharding(mesh, spec)
        if name == "ssm_h":
            L_, b, di, n = x.shape
            spec = P(None, batch_axes(b), TP if di % tp == 0 else None, None)
            return NamedSharding(mesh, spec)
        if name == "conv":
            L_, b, w, di = x.shape
            spec = P(None, batch_axes(b), None, TP if di % tp == 0 else None)
            return NamedSharding(mesh, spec)
        return NamedSharding(mesh, P(*((None,) * x.ndim)))

    return jax.tree_util.tree_map_with_path(leaf, cache)
