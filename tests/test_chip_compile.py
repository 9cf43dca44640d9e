"""Compile the main-path programs for a described TPU v5e (no chip needed).

Nothing runs: the TPU compiler refuses what the chip would refuse (a
program that does not fit its memory, a sharding it cannot partition) at
no chip time.  The topology is described inside a module fixture, never at
import, because only one process at a time may load the TPU library.
"""

import base64
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


def _step(topo, policy, n=N, nb=NB, off_update="chunked"):
    one = SingleDeviceSharding(topo.devices[0])
    spec = partial(jax.ShapeDtypeStruct, sharding=one)
    fn = jax.jit(partial(geostat_loglik_step, nb=nb, policy=policy,
                         nu_static=0.5, off_update=off_update))
    return fn.lower(spec((n, 2), jnp.float32), spec((n,), jnp.float32),
                    spec((2,), jnp.float32)).compile()


def _update_lo_ops(compiled) -> list:
    """The compiled program's instructions under the `update_lo` scope."""
    return [line for line in compiled.as_text().splitlines()
            if "/factor/update_lo/" in line and " = " in line]


def test_lo_update_is_one_kernel_per_step_on_v5e(topo):
    """bf16 tiles of 512: each step whose trapezoid is not empty (k = 0
    alone at p = 4, t = 2) makes one kernel call and no GEMM of its own,
    and the program holds no more temporaries than the square's."""
    policy = PrecisionPolicy.tpu(2)
    compiled = _step(topo, policy)
    ops = _update_lo_ops(compiled)
    calls = [op for op in ops if 'custom_call_target="tpu_custom_call"' in op]
    assert len(calls) == 1
    assert all("/update_lo/" in op and "lo_trailing_update" in op
               for op in calls)
    assert not [op for op in ops if re.search(r" (dot|convolution)\(", op)]
    square = _step(topo, policy, off_update="square")
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= square.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("policy,nb", [
    (PrecisionPolicy(mode="mixed", hi=jnp.float32, lo=jnp.float32,
                     diag_thick=2), NB),
    (PrecisionPolicy.tpu(2), 64)], ids=["f32_lo", "nb64"])
def test_lo_update_takes_the_loop_off_bf16_or_128_on_v5e(topo, policy, nb):
    ops = _update_lo_ops(_step(topo, policy, n=8 * nb, nb=nb))
    assert ops and not [op for op in ops if "tpu_custom_call" in op]


def _kernel_text(match) -> str:
    """A Pallas kernel's serialized Mosaic module, printed without its
    source locations, which hold the named scopes of the call around it."""
    from jax.extend.mlir import ir
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        return module.operation.get_asm(enable_debug_info=False)


def _program_text(compiled) -> str:
    """A compiled program's HLO without `op_name` metadata, without the
    source-location tables that the metadata points into, and with each
    kernel's body printed without its locations."""
    lines = [line for line in compiled.as_text().splitlines()
             if line.startswith(("HloModule", "%", "ENTRY", " ", "}"))]
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))
    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _kernel_text, text)


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
