"""Readings of the check's numbers for the program, the control and a fault.

The control is the reference put in the program's place, computed in the
precision below the configuration's (its ``control_precision``: ``high``,
three bfloat16 passes, for float32 at ``highest``), and compared with the
reference at ``highest`` exactly as a run's check compares the program. The
fault ``half_batch`` leaves out the second half of each step's rows and
takes the mean over the rest. A step that returns its state unchanged
reads about 1 on the change's gap by the measure itself and needs no run.
``program`` is the program's own jitted step (built as a run builds it) on
the same batches, so that the program's readings over many seeds and the
control's come from one process. Each reading uses the first three steps of
rank 0's plan slice at the cell's own sizes, padded to its capacity, from
the seed, as a run does; the window is not needed.
"""
from __future__ import annotations

import functools
import json
import math

import numpy as np

from bench.harness import checks, data, reference
from bench.harness.cell import loader_spec, program_step, reference_batches

__all__ = ["readings"]


def _compare(net, cfg, params0, ref, other) -> dict:
    losses, g, p3 = other
    ref_losses, ref_g, ref_p3 = ref
    p0 = reference.leaves(params0)
    g_ref = reference.leaves(ref_g)
    d3 = [a - b for a, b in zip(reference.leaves(p3), p0)]
    d3_ref = [a - b for a, b in zip(reference.leaves(ref_p3), p0)]
    keep = checks.steady_leaves(g_ref)
    out = {"grad_leaf_gaps": checks.leaf_gaps(reference.leaves(g), g_ref).tolist(),
           "update_leaf_gaps": checks.leaf_gaps(d3, d3_ref).tolist(),
           "loss_gap": checks.loss_gap(losses, ref_losses)}
    for name in cfg["limits"]:
        if name != "loss_gap":
            gaps = out["grad_leaf_gaps"] if name.startswith("grad") else out["update_leaf_gaps"]
            out[name] = checks.held_gap(name, gaps, None if name.startswith("grad") else keep,
                                        net.last_layer(cfg))
    return out


@functools.lru_cache(maxsize=None)
def _program_step(cfg_json: str):
    return program_step(json.loads(cfg_json))


def _program(cfg, params0, batches, rows: int):
    """The program's step over ``batches``: ``(losses, first_grad,
    params_after)`` as the run's check takes them."""
    import jax

    from repro.train.step import init_train_state

    step, opt = _program_step(json.dumps(cfg, sort_keys=True))
    state = init_train_state(jax.device_put(params0), opt)
    losses, g1 = [], None
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for x, y in batches:
            w = np.zeros(rows, np.float32)
            w[: len(x)] = 1.0
            state, m = step(state, {"x": reference.pad_rows(x, rows),
                                    "y": reference.pad_rows(y, rows), "weights": w})
            losses.append(float(m["loss"]))
            if g1 is None:
                # AdamW's first moment after one step is (1 - b1) * its gradient
                g1 = jax.tree_util.tree_map(lambda a: a / (1 - opt.b1),
                                            jax.device_get(state["opt"].mu))
    return losses, g1, jax.device_get(state["params"])


def readings(cell, seed: int) -> dict:
    """``{"program": {...}, "control": {...}, "half_batch": {...}}`` for one seed."""
    import jax

    from repro.data import plan

    cfg, wl, net = cell.config, cell.workload, cell.network
    n = int(cfg["num_samples"])
    mine = plan(loader_spec(cfg, wl), num_samples=n).for_node(wl["rank"])
    table = (data.targets(seed, n, math.prod(cfg["output_shape"]))
             if cfg["targets"] == "seeded" else None)
    batches = reference_batches(
        cfg, seed, [sp.nodes[0].sample_ids for ep in mine.epochs for sp in ep.steps][:3], table)
    params0 = jax.device_get(jax.jit(lambda k: net.init_params(k, cfg))(
        reference.jax_key(seed)))
    rows = mine.capacity
    with jax.default_matmul_precision("highest"):
        ref = reference.train_steps(net, params0, batches, cfg, rows)
        half = reference.train_steps(
            net, params0, [(x[: max(1, len(x) // 2)], y[: max(1, len(y) // 2)])
                           for x, y in batches], cfg, rows)
    with jax.default_matmul_precision(cfg["control_precision"]):
        control = reference.train_steps(net, params0, batches, cfg, rows)
    return {"program": _compare(net, cfg, params0, ref, _program(cfg, params0, batches, rows)),
            "control": _compare(net, cfg, params0, ref, control),
            "half_batch": _compare(net, cfg, params0, ref, half),
            "rows": [len(x) for x, _ in batches]}
