"""The plain reference against the program's model, at small sizes on the CPU.

Every configuration of BENCHMARK.json is tested, with the network module its
file names; the CPU sizes are its file's ``tiny`` block."""
import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import catalog, checks, data, reference
from bench.harness.cell import program_config, reference_batches
from repro.models import cnn
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_train_state, make_train_step

ROOT = Path(__file__).resolve().parents[2]
import conftest  # noqa: E402

CONFIGS = sorted(conftest.CONFIG_FILES)


def _network(cfg):
    return catalog.load_network(ROOT, cfg["network"])


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_tree_is_the_programs(name):
    cfg = conftest.config(name)
    want = jax.eval_shape(lambda: cnn.init_surrogate(jax.random.PRNGKey(0),
                                                     program_config(cfg)))
    got = jax.eval_shape(lambda: _network(cfg).init_params(reference.jax_key(1), cfg))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert [a.shape for a in jax.tree_util.tree_leaves(want)] == [
        a.shape for a in jax.tree_util.tree_leaves(got)]
    if "parameters" in cfg:
        assert sum(a.size for a in jax.tree_util.tree_leaves(got)) == cfg["parameters"]


def test_seeds_differing_in_high_bits_give_different_weights():
    cfg = conftest.tiny_config("ptychonn_repo")
    net = _network(cfg)
    a = net.init_params(reference.jax_key(7), cfg)
    b = net.init_params(reference.jax_key(7 + 2**33), cfg)
    assert not np.array_equal(a["enc"][0]["w"], b["enc"][0]["w"])


def _three_steps(cfg, seed=3):
    """The reference's three AdamW steps on 5 rows each, padded to 8."""
    net = _network(cfg)
    params = net.init_params(reference.jax_key(seed), cfg)
    table = (data.targets(seed, cfg["num_samples"], int(np.prod(cfg["output_shape"])))
             if cfg["targets"] == "seeded" else None)
    batches = reference_batches(cfg, seed, [np.arange(s * 5, s * 5 + 5) for s in range(3)],
                                table)
    with jax.default_matmul_precision("highest"):
        return params, batches, reference.train_steps(net, params, batches, cfg, rows=8)


@pytest.mark.parametrize("name", CONFIGS)
def test_three_adamw_steps_match_the_program(name):
    cfg = conftest.tiny_config(name)
    scfg = program_config(cfg)
    params, batches, (losses, g1, p3) = _three_steps(cfg)
    with jax.default_matmul_precision("highest"):
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


@pytest.mark.parametrize("shape,n,seed,digest", [
    ((8, 8, 3), 32771, 11, "0d543aed428f06a0ea6b917ce9ebec772bd4eba3cfe9d3909b928f201a89586a"),
    ((16, 16, 16, 4), 40, 2**33 + 5,
     "b4560783c914748d330213c03068ac5af4df101ceb4218fda314824087a703a8"),
])
def test_float32_records_are_the_same_bytes(shape, n, seed, digest):
    """The default records, byte for byte as before ``record_dtype`` (the
    digests were read from the tree before it)."""
    a = data.generate(seed, n, shape, threads=3)
    assert a.dtype == np.float32 and data.record_dtype({}) == "float32"
    assert data.block_rows(shape) == data.block_rows(shape, "float32")
    assert hashlib.sha256(a.tobytes()).hexdigest() == digest


def test_int16_records_are_counts_made_again_from_the_seed():
    shape = (16, 16, 16, 4)
    rows = data.block_rows(shape, "int16")
    assert rows == 2 * data.block_rows(shape)  # by the element's size, 2 bytes
    n = 2 * rows + 9
    full = data.generate(2**33 + 7, n, shape, threads=3, dtype="int16")
    assert full.dtype == np.int16 and full.min() >= 0
    # P(k) = 2**-(k+1): half the counts are 0, the mean is 1
    assert abs(np.mean(full == 0) - 0.5) < 0.01 and abs(full.mean() - 1.0) < 0.02
    ids = np.array([n - 1, 0, rows, rows - 1, 2 * rows + 3, 5])
    again = data.records(2**33 + 7, ids, shape, "int16")
    assert again.dtype == np.int16
    np.testing.assert_array_equal(again, full[ids])
    assert not np.array_equal(data.generate(2**33 + 8, n, shape, dtype="int16"), full)
    with pytest.raises(ValueError):
        data.record_dtype({"record_dtype": "float16"})


#: the parent tree's readings of each configuration's network, before the
#: network moved into its own module: sha256 of the float32 bytes of the
#: full-size initial weights from seed 2**33 + 17, and the tiny size's three
#: reference losses (``_three_steps``).
PINNED = {
    "ptychonn_repo": ("1ce4223fcfe719bc3ce44e0a3480522ca1e39ee8425f1fb8520384993baed950",
                      [1.0045257806777954, 1.0057041645050049, 1.0049844980239868]),
    "cosmoflow_repo": ("272a9491ddbd8885c7a1ca7e84acbd4603af14f6e16a9d2f67c2bf8db227113f",
                       [1.0544559955596924, 1.05641770362854, 0.5087229609489441]),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_and_losses_are_the_parents(name):
    digest, losses = PINNED[name]
    cfg = conftest.config(name)
    params = jax.jit(lambda k: _network(cfg).init_params(k, cfg))(reference.jax_key(2**33 + 17))
    h = hashlib.sha256()
    for a in reference.leaves(params):
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == digest
    np.testing.assert_allclose(_three_steps(conftest.tiny_config(name))[2][0], losses,
                               rtol=1e-6)


@pytest.mark.parametrize("name,last", [("ptychonn_repo", (("dec", -1, "b"), ("dec", -1, "w"))),
                                       ("cosmoflow_repo", (("head", "b2"), ("head", "w2")))])
def test_last_layer_marks_the_leaves_next_to_the_loss(name, last):
    cfg = conftest.config(name)
    params = jax.eval_shape(lambda: _network(cfg).init_params(reference.jax_key(1), cfg))
    marked = [a for a, m in zip(jax.tree_util.tree_leaves(params),
                                _network(cfg).last_layer(cfg)) if m]

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
