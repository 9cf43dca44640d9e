"""Compile the main-path programs for a described TPU v5e (no chip needed).

Nothing runs: the TPU compiler refuses what the chip would refuse (a
program that does not fit its memory, a sharding it cannot partition) at
no chip time.  The topology is described inside a module fixture, never at
import, because only one process at a time may load the TPU library.
"""

import base64
import contextlib
import math
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


def _sharded(topo, fn, n, *, args=("locs", "z", "theta")):
    """`fn` compiled on the described 2x2 mesh with the benchmark's input
    shardings: `locs` and `z` rows over "data", theta on every chip."""
    mesh = make_geostat_mesh(topo.devices)
    spec = lambda shape, *axes: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=NamedSharding(mesh, P(*axes)))
    specs = {"locs": spec((n, 2), "data", None), "z": spec((n,), "data"),
             "theta": spec((2,))}
    set_activation_mesh(mesh)
    try:
        return jax.jit(fn).lower(*(specs[a] for a in args)).compile()
    finally:
        set_activation_mesh(None)


@pytest.mark.parametrize("version", ["fori", "masked_full"])
def test_distributed_loglik_is_sharded_over_v5e_2x2(topo, version):
    policy = PrecisionPolicy.tpu(2)
    compiled = _sharded(topo, partial(geostat_loglik_distributed, nb=NB,
                                      policy=policy, nu_static=0.5,
                                      version=version), N)
    build = _sharded(topo, partial(build_covariance_distributed, nb=NB,
                                   policy=policy, nu_static=0.5), N,
                     args=("locs", "theta"))
    # constrain() split the (n, n) off-band storage over all 4 devices;
    # the row-sharded inputs alone would leave each device n/2 full rows
    assert build.output_shardings[0].shard_shape((N, N)) == (N // 2, N // 2)
    assert 0 < _bytes(compiled) < V5E_HBM_BYTES


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%\S+ = (.+?) ([a-z-]+)\(")
_COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
                "all-to-all", "reduce-scatter")


def _largest_array(result_type: str) -> tuple:
    """(bytes, elements) of the largest array in an HLO result type; a
    `-start` op's tuple holds its operand and its result."""
    arrays = [(_DTYPE_BYTES[dt], math.prod(int(d) for d in dims.split(",")
                                           if d))
              for dt, dims in re.findall(r"\b([a-z0-9]+)\[([0-9,]*)\]",
                                         result_type) if dt in _DTYPE_BYTES]
    return max(((b * e, e) for b, e in arrays), default=(0, 0))


def test_sharded_sweep_moves_tiles_not_the_band_on_v5e_2x2(topo):
    """The `fori` sweep reads and writes each band tile at the traced step
    on the chips that hold it.  At p = 8 a tile is a sixteenth of the f32
    band: no collective under the loop bodies' `potrf`, `trsm_hi` and
    `gather`, or in the solve's loop, returns more than a step's t tiles
    per chip; none under `update_hi`, where the panel becomes whole on
    every chip, returns more than the (n, nb) panel in bf16; and no copy
    under `potrf` or `trsm_hi` is as large as a chip's share of the
    band."""
    n, nb, policy = 4096, 512, PrecisionPolicy.tpu(2)
    p, t = n // nb, policy.diag_thick
    compiled = _sharded(topo, partial(geostat_loglik_distributed, nb=nb,
                                      policy=policy, nu_static=0.5,
                                      version="fori"), n)
    body = "/factor/while/body/closed_call/"
    moving = tuple(body + phase + "/" for phase in
                   ("potrf", "trsm_hi", "gather")) + ("/solve/while/body/",)
    copying = (body + "potrf/", body + "trsm_hi/")
    moved, panel, copied = [], [], []
    for line in compiled.as_text().splitlines():
        op = _INSTRUCTION.match(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not (op and name):
            continue
        nbytes, elems = _largest_array(op.group(1))
        if (op.group(2).startswith(_COLLECTIVES)
                and any(s in name.group(1) for s in moving)):
            moved.append((nbytes, name.group(1)))
        if (op.group(2).startswith(_COLLECTIVES)
                and body + "update_hi/" in name.group(1)):
            panel.append((nbytes, name.group(1)))
        if (op.group(2) in ("copy", "copy-start")
                and any(s in name.group(1) for s in copying)):
            copied.append((elems, name.group(1)))
    assert moved                      # the phases are found in the program
    assert max(moved)[0] <= t * nb * nb * 4, max(moved)
    assert max(panel, default=(0, ""))[0] <= n * nb * 2, max(panel)
    assert max(copied, default=(0, ""))[0] < p * t * nb * nb // 4, max(copied)


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
