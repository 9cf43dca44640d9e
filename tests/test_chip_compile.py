"""Compile the main-path programs for a described TPU v5e (no chip needed).

Nothing runs: the TPU compiler refuses what the chip would refuse (a
program that does not fit its memory, a sharding it cannot partition) at
no chip time.  The topology is described inside a module fixture, never at
import, because only one process at a time may load the TPU library.
"""

import contextlib
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import PrecisionPolicy
from repro.core.distributed import (build_covariance_distributed,
                                    geostat_loglik_distributed)
from repro.core.kriging import krige
from repro.core.panel_cholesky import geostat_loglik_step
from repro.launch.mesh import make_geostat_mesh
from repro.models.sharding import set_activation_mesh

N, NB = 2048, 512            # p = 4 tiles
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else libtpu logs under /tmp
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


def _bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)


@pytest.mark.parametrize("policy", [PrecisionPolicy.full(),
                                    PrecisionPolicy.tpu(2)],
                         ids=["full", "tpu2"])
def test_loglik_step_compiles_for_one_v5e(topo, policy):
    one = SingleDeviceSharding(topo.devices[0])
    spec = partial(jax.ShapeDtypeStruct, sharding=one)
    fn = jax.jit(partial(geostat_loglik_step, nb=NB, policy=policy,
                         nu_static=0.5))
    compiled = fn.lower(spec((N, 2), jnp.float32), spec((N,), jnp.float32),
                        spec((2,), jnp.float32)).compile()
    assert 0 < _bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("version", ["fori", "masked_full"])
def test_distributed_loglik_is_sharded_over_v5e_2x2(topo, version):
    mesh = make_geostat_mesh(topo.devices)
    spec = lambda shape, *axes: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=NamedSharding(mesh, P(*axes)))
    fn = jax.jit(partial(geostat_loglik_distributed, nb=NB,
                         policy=PrecisionPolicy.tpu(2), nu_static=0.5,
                         version=version))
    build = jax.jit(partial(build_covariance_distributed, nb=NB,
                            policy=PrecisionPolicy.tpu(2), nu_static=0.5))
    locs, z, theta = (spec((N, 2), "data", None), spec((N,), "data"),
                      spec((2,)))
    set_activation_mesh(mesh)
    try:
        compiled = fn.lower(locs, z, theta).compile()
        off_sharding = build.lower(locs, theta).compile().output_shardings[0]
    finally:
        set_activation_mesh(None)
    # constrain() split the (n, n) off-band storage over all 4 devices;
    # the row-sharded inputs alone would leave each device n/2 full rows
    assert off_sharding.shard_shape((N, N)) == (N // 2, N // 2)
    assert 0 < _bytes(compiled) < V5E_HBM_BYTES


def _program_text(compiled) -> str:
    """A compiled program's HLO without `op_name` metadata and without the
    source-location tables that the metadata points into."""
    lines = [line for line in compiled.as_text().splitlines()
             if line.startswith(("HloModule", "%", "ENTRY", " ", "}"))]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.mark.parametrize("which", ["eval", "krige"])
def test_named_scopes_change_no_operation_on_v5e(topo, monkeypatch, which):
    """The named scopes are metadata alone for the chip's compiler too."""
    one = SingleDeviceSharding(topo.devices[0])
    spec = partial(jax.ShapeDtypeStruct, sharding=one)
    policy = PrecisionPolicy.tpu(2)
    if which == "eval":
        fn = partial(geostat_loglik_step, nb=NB, policy=policy,
                     nu_static=0.5)
        args = (spec((N, 2), jnp.float32), spec((N,), jnp.float32),
                spec((2,), jnp.float32))
    else:
        def fn(locs, z, new, theta):
            return krige(locs[:N], z[:N], locs[new], theta, policy, nb=NB,
                         nu_static=0.5)
        args = (spec((N + NB, 2), jnp.float32), spec((N + NB,), jnp.float32),
                spec((NB,), jnp.int32), spec((2,), jnp.float32))
    scoped = jax.jit(fn).lower(*args).compile()
    assert "/factor/potrf/" in scoped.as_text()
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = jax.jit(fn).lower(*args).compile()
    assert "/factor/potrf/" not in plain.as_text()
    assert _program_text(scoped) == _program_text(plain)
