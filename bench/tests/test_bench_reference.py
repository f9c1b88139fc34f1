"""The plain reference against the program's model, at small sizes on the CPU."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import checks, data, reference
from bench.harness.cell import _surrogate_config
from repro.models import cnn
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_train_state, make_train_step

ROOT = Path(__file__).resolve().parents[2]
import conftest  # noqa: E402


@pytest.mark.parametrize("name", ["ptychonn_repo", "cosmoflow_repo"])
def test_reference_tree_is_the_programs(name):
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    want = jax.eval_shape(lambda: cnn.init_surrogate(jax.random.PRNGKey(0),
                                                     _surrogate_config(cfg)))
    got = jax.eval_shape(lambda: reference.init_params(reference.jax_key(1), cfg))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert [a.shape for a in jax.tree_util.tree_leaves(want)] == [
        a.shape for a in jax.tree_util.tree_leaves(got)]
    if "parameters" in cfg:
        assert sum(a.size for a in jax.tree_util.tree_leaves(got)) == cfg["parameters"]


def test_seeds_differing_in_high_bits_give_different_weights():
    cfg = conftest.tiny_config("ptychonn_repo")
    a = reference.init_params(reference.jax_key(7), cfg)
    b = reference.init_params(reference.jax_key(7 + 2**33), cfg)
    assert not np.array_equal(a["enc"][0]["w"], b["enc"][0]["w"])


@pytest.mark.parametrize("name", ["ptychonn_repo", "cosmoflow_repo"])
def test_three_adamw_steps_match_the_program(name):
    cfg = conftest.tiny_config(name)
    scfg = _surrogate_config(cfg)
    params = reference.init_params(reference.jax_key(3), cfg)
    table = data.targets(3, cfg["num_samples"], int(np.prod(cfg["output_shape"])))
    batches = []
    for s in range(3):
        ids = np.arange(s * 5, s * 5 + 5)
        rec = data.records(3, ids, cfg["record_shape"])
        if cfg["targets"] == "record":
            batches.append((rec[..., :1], rec[..., 1:]))
        else:
            batches.append((rec, table[ids]))
    with jax.default_matmul_precision("highest"):
        losses, g1, p3 = reference.train_steps(params, batches, cfg, rows=8)
        opt = AdamWConfig(**cfg["optimizer"])
        step = jax.jit(make_train_step(
            type("C", (), {"grad_accum": 1, "grad_accum_dtype": "float32"}), opt,
            lambda p, b: cnn.surrogate_loss(p, b, scfg)))
        state = init_train_state(params, opt)
        prog = []
        for x, y in batches:
            pad = 3  # padding rows carry weight 0 and change nothing
            b = {"x": jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:])]),
                 "y": jnp.concatenate([y, jnp.zeros((pad,) + y.shape[1:])]),
                 "weights": jnp.concatenate([jnp.ones(5), jnp.zeros(pad)])}
            state, m = step(state, b)
            prog.append(float(m["loss"]))
            if len(prog) == 1:
                mu1 = jax.device_get(state["opt"].mu)
    np.testing.assert_allclose(prog, losses, rtol=1e-5)
    for a, b in zip(reference.leaves(mu1), reference.leaves(g1)):
        np.testing.assert_allclose(a / (1 - opt.b1), b, rtol=1e-4, atol=1e-7)
    for a, b in zip(reference.leaves(state["params"]), reference.leaves(p3)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_records_are_made_again_from_the_seed_alone():
    shape = (8, 8, 3)
    rows = data.block_rows(shape)
    n = 3 * rows + 5
    full = data.generate(11, n, shape, threads=3)
    ids = np.array([n - 1, 0, rows, rows - 1, 2 * rows + 7, 5])
    np.testing.assert_array_equal(data.records(11, ids, shape), full[ids])
    assert not np.array_equal(data.generate(12, n, shape), full)


@pytest.mark.parametrize("name,last", [("ptychonn_repo", (("dec", -1, "b"), ("dec", -1, "w"))),
                                       ("cosmoflow_repo", (("head", "b2"), ("head", "w2")))])
def test_last_layer_marks_the_leaves_next_to_the_loss(name, last):
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    params = jax.eval_shape(lambda: reference.init_params(reference.jax_key(1), cfg))
    marked = [a for a, m in zip(jax.tree_util.tree_leaves(params), reference.last_layer(cfg))
              if m]

    def at(path):
        node = params
        for k in path:
            node = node[k]
        return node
    assert [a.shape for a in marked] == [at(p).shape for p in last]


def test_last_layer_limit_holds_only_the_marked_leaves():
    gaps = [9.0, 1.0, 2.0, 8.0]
    last = [False, True, True, False]
    assert checks.held_gap("grad_gap_last_layer_worst_leaf", gaps, last_layer=last) == 2.0
    assert checks.held_gap("grad_gap_worst_leaf", gaps, last_layer=last) == 9.0
    with pytest.raises(ValueError):
        checks.held_gap("grad_gap_last_layer_worst_leaf", gaps)
