"""Sharding rule-engine tests on a faked 16x16 / 2x16x16 mesh (no devices
needed: the rules only read axis names + sizes)."""
import types

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as S


def fake_mesh(shape, names):
    return types.SimpleNamespace(
        axis_names=names, devices=np.empty(shape), shape=dict(zip(names, shape))
    )


POD = fake_mesh((2, 16, 16), ("pod", "data", "model"))
SINGLE = fake_mesh((16, 16), ("data", "model"))


def spec_for(path, shape, mesh=SINGLE):
    return S._spec_for(path, shape, mesh)


def test_llama_attention_rules():
    # wq [L, d, H, hd]: d over fsdp axes, heads over model
    assert spec_for("layers/wq", (126, 16384, 128, 128)) == P(
        None, "data", "model", None
    )
    # multi-pod: fsdp spans pod+data
    assert spec_for("layers/wq", (126, 16384, 128, 128), POD) == P(
        None, ("pod", "data"), "model", None
    )
    # wk with K=8 (not divisible by 16): TP axis dropped, FSDP kept
    sp = spec_for("layers/wk", (126, 16384, 8, 128))
    assert sp == P(None, "data", None, None)
    # wo row-parallel over heads, fsdp on d
    assert spec_for("layers/wo", (126, 128, 128, 16384)) == P(
        None, "model", None, "data"
    )


def test_awkward_head_counts_degrade_gracefully():
    # hymba: 25 heads, d=1600 — heads unshardable, d stays FSDP-sharded
    sp = spec_for("layers/wq", (32, 1600, 25, 64))
    assert sp == P(None, "data", None, None)
    # qwen2-0.5b wk: K=2, d=896 (896 % 16 == 0)
    sp = spec_for("layers/wk", (24, 896, 2, 64))
    assert sp == P(None, "data", None, None)


def test_embedding_rules_single_axis():
    # vocab over model ONLY (two-axis sharding forces batch-replicated ARs)
    assert spec_for("embed", (128256, 16384)) == P("model", None)
    assert spec_for("unembed", (16384, 128256)) == P(None, "model")
    # odd vocab (whisper 51865): falls to the fsdp candidate or replication
    sp = spec_for("embed", (51865, 1024))
    assert sp[0] is None  # 51865 is odd -> vocab unsharded


def test_moe_expert_parallel():
    assert spec_for("layers/we_gate", (32, 16, 4096, 6400)) == P(
        None, "model", "data", None
    )
    # 64 padded experts for qwen2-moe
    assert spec_for("layers/we_down", (24, 64, 1408, 2048)) == P(
        None, "model", None, "data"
    )


def test_ssm_rules():
    assert spec_for("layers/ssm/in_proj", (64, 4096, 16384)) == P(
        None, "data", "model"
    )
    assert spec_for("layers/ssm/out_proj", (64, 8192, 4096)) == P(
        None, "model", "data"
    )
    assert spec_for("layers/ssm/a_log", (64, 8192, 16)) == P(None, "model", None)


def test_norms_replicated():
    assert spec_for("layers/ln1", (126, 16384)) == P(None, None)
    assert spec_for("final_norm", (16384,)) == P(None)


def test_choose_spec_drops_missing_axes():
    sp = S.choose_spec((128, 64), [(("pod", "data"), "model")], SINGLE)
    assert sp == P("data", "model")


def test_choose_spec_divisibility():
    # dim 100 not divisible by 16: axis dropped
    sp = S.choose_spec((100, 64), [("data", "model")], SINGLE)
    assert sp == P(None, "model")


@pytest.mark.parametrize("context", ["none", "with_mesh", "set_mesh"])
def test_constrain_resolves_the_ambient_mesh(context):
    """``constrain`` pins activations under both mesh contexts: the
    abstract mesh of ``jax.sharding.set_mesh`` and the thread-resources
    context of ``with mesh:`` (which launch/dryrun.py uses); outside any
    mesh it is a no-op."""
    import contextlib

    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    ctx = {
        "none": contextlib.nullcontext,
        "with_mesh": lambda: mesh,
        "set_mesh": lambda: jax.sharding.set_mesh(mesh),
    }[context]
    with ctx():
        text = jax.jit(lambda x: S.constrain(x, "data", None) * 2).lower(
            np.ones((4, 4), np.float32)
        ).as_text()
    assert ("sharding_constraint" in text) == (context != "none")
