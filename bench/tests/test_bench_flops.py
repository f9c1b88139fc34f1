"""The FLOP counter against XLA's cost analysis, on the CPU.

The border bound is the geometry of ``bench/networks/cnn_repo.py`` (stride-2
3^d convolutions), so those tests run every configuration that names it."""
import jax
import jax.numpy as jnp
import pytest

import conftest
from bench.harness import catalog, reference

ROOT = conftest.ROOT
CNN_REPO = "bench/networks/cnn_repo.py"
cnn_repo = catalog.load_network(ROOT, CNN_REPO)


def _cost(fn, *args) -> float:
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("rank,n", [(2, 8), (2, 64), (3, 8)])
def test_one_convolution_each_way(rank, n):
    """Per dimension, XLA counts the 3n - 1 taps inside the input where the
    model count takes 3n (n: outputs of the convolution, inputs of the
    transposed one)."""
    x = jnp.ones((1,) + (n,) * rank + (4,))
    w = jnp.ones((3,) * rank + (4, 6))
    down = _cost(lambda a, b: cnn_repo._down(a, b, rank), x, w)
    assert down == 2 * 4 * 6 * (3 * n // 2 - 1) ** rank
    up = _cost(lambda a, b: cnn_repo._up(a, b, rank), x, w)
    assert up == 2 * 4 * 6 * (3 * n - 1) ** rank


@pytest.mark.parametrize("name", sorted(n for n, f in conftest.CONFIG_FILES.items()
                                        if conftest.config(n)["network"] == CNN_REPO))
def test_forward_matches_cost_analysis_but_the_borders(name):
    cfg = conftest.tiny_config(name)
    p = cnn_repo.init_params(reference.jax_key(0), cfg)
    x = jnp.ones((2,) + tuple(cfg["input_shape"]))
    xla = _cost(lambda q, a: cnn_repo.forward(q, a, cfg), p, x) / 2
    model = cnn_repo.forward_flops(cfg)
    # the smallest layer here is 4 wide: its border costs it (11/12)^d
    rank = len(cfg["input_shape"]) - 1
    assert model * (11 / 12) ** rank <= xla <= model * 1.02
    assert cnn_repo.train_flops_per_sample(cfg) == 3 * model


def test_ptychonn_at_its_size():
    cfg = conftest.tiny_config("ptychonn_repo") | {
        "input_shape": [64, 64, 1], "output_shape": [64, 64, 2], "base_channels": 64,
        "depth": 3}
    p = jax.eval_shape(lambda: cnn_repo.init_params(reference.jax_key(0), cfg))
    x = jax.ShapeDtypeStruct((1, 64, 64, 1), jnp.float32)
    xla = jax.jit(lambda q, a: cnn_repo.forward(q, a, cfg)).lower(p, x).compile(
        ).cost_analysis()["flops"]
    assert cnn_repo.forward_flops(cfg) == pytest.approx(xla, rel=0.1)


@pytest.mark.parametrize("name,flops", [("ptychonn_repo", 463_601_664),
                                        ("cosmoflow_repo", 7_524_584_448)])
def test_train_flops_per_sample_are_the_parents(name, flops):
    """The model FLOPs ``step_mfu`` reads at each configuration's full size,
    as the tree before the network's own module counted them."""
    cfg = conftest.config(name)
    assert catalog.load_network(ROOT, cfg["network"]).train_flops_per_sample(cfg) == flops
