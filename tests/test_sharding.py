"""Logical-axis sharding resolution + divisibility fallbacks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_smoke_mesh
from repro.models.sharding import (DEFAULT_RULES, ax, batch_spec, constrain,
                                   resolve_spec, set_activation_mesh)


@pytest.fixture()
def mesh2x2():
    if jax.device_count() < 1:
        pytest.skip("no devices")
    return make_smoke_mesh()


def test_resolve_basic(mesh2x2):
    spec = resolve_spec(ax("vocab", "embed"), mesh2x2, shape=(1024, 64))
    assert spec == P("model", "data")


def test_resolve_divisibility_fallback(mesh2x2):
    # 1-device axes always divide; simulate a fat mesh via a fake object
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16))
    spec = resolve_spec(ax("kv_heads", "head_dim"), FakeMesh(),
                        shape=(8, 128))  # 8 kv heads % 16 != 0
    assert spec[0] is None

    spec2 = resolve_spec(ax("experts", "embed", "expert_ffn"), FakeMesh(),
                         shape=(8, 6144, 32768))  # grok: expert_ffn takes TP
    assert spec2[0] is None and spec2[1] == "data" and spec2[2] == "model"

    spec3 = resolve_spec(ax("experts", "embed", "expert_ffn"), FakeMesh(),
                         shape=(128, 2048, 768))  # qwen3-moe: EP wins
    assert spec3[0] == "model"


def test_multi_axis_placement():
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        devices = np.empty((2, 16, 16))
    spec = resolve_spec(ax("batch", "."), FakeMesh(), shape=(256, 128))
    assert spec[0] == ("pod", "data")
    flat = resolve_spec(ax("act_expert_flat", "."), FakeMesh(),
                        shape=(327680, 6144))
    assert flat[0] == ("model", "data")


def test_constrain_noop_without_mesh():
    set_activation_mesh(None)
    x = jnp.ones((4, 4))
    assert constrain(x, ax("act_batch", ".")) is x


def test_constrain_with_mesh(mesh2x2):
    set_activation_mesh(mesh2x2)
    try:
        x = jnp.ones((4, 4))
        y = jax.jit(lambda a: constrain(a, ax("act_batch", ".")))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    finally:
        set_activation_mesh(None)


def test_set_activation_mesh_rejects_explicit_axes():
    from jax.sharding import AxisType
    explicit = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Explicit,) * 2)
    with pytest.raises(ValueError, match="Auto mesh axes"):
        set_activation_mesh(explicit)
    set_activation_mesh(None)


def test_batch_spec_seq_sharded(mesh2x2):
    assert batch_spec(mesh2x2) == P(("data",))
    assert batch_spec(mesh2x2, seq_sharded=True) == P(None, ("data",))
