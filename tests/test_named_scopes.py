"""Named scopes of the compiled likelihood step, the sharded likelihood
and kriging (DESIGN.md §13).

The scopes are HLO `op_name` metadata and nothing else: with `jax.named_scope`
replaced by a null context, the lowered and the compiled programs are the
same once metadata and source locations are left out.  Every phase name
is present in the compiled program's metadata, so a profiler trace can
sum device time per phase.
"""

import contextlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import geostat_loglik_distributed
from repro.core.kriging import krige
from repro.core.panel_cholesky import geostat_loglik_step
from repro.core.precision import PrecisionPolicy

N, NB = 256, 32
POLICY = PrecisionPolicy(mode="mixed", hi=jnp.float32, lo=jnp.bfloat16,
                         diag_thick=2, solve_dtype=jnp.float32,
                         accum_dtype=jnp.float32)

PANEL_PHASES = ("potrf", "trsm_hi", "trsm_lo", "gather", "update_hi",
                "update_lo")
TILE_PHASES = ("potrf", "trsm_hi", "trsm_lo", "update_hi", "update_lo",
               "convert")
SCOPES = {
    "eval": ["geostat_loglik_step/cov_build", "geostat_loglik_step/factor",
             "geostat_loglik_step/solve"]
    + [f"geostat_loglik_step/factor/{p}" for p in PANEL_PHASES],
    "krige": ["krige/cov_build", "krige/factor", "krige/solve"]
    + [f"krige/factor/{p}" for p in TILE_PHASES],
    "dist": ["geostat_loglik_distributed/cov_build",
             "geostat_loglik_distributed/factor",
             "geostat_loglik_distributed/solve"],
}
# inside the sharded `fori` sweep the phases sit in the loop's body, under
# `factor/while/body/...`
DIST_PHASES = PANEL_PHASES


def _inputs():
    rng = np.random.default_rng(3)
    locs = jnp.asarray(rng.random((N, 2)), jnp.float32)
    z = jnp.asarray(rng.standard_normal(N), jnp.float32)
    return locs, z, jnp.asarray([1.0, 0.03], jnp.float32)


def _program(which, off_update="square"):
    """The step or the kriging request as the benchmark jits them, with
    the field, theta and (kriging) the split as arguments."""
    locs, z, theta = _inputs()
    if which == "eval":
        def fn(locs, z, theta):
            return geostat_loglik_step(locs, z, theta, nb=NB, policy=POLICY,
                                       nu_static=0.5, jitter=1e-6,
                                       off_update=off_update)
        return fn, (locs, z, theta)
    if which == "dist":
        def fn(locs, z, theta):
            return geostat_loglik_distributed(locs, z, theta, nb=NB,
                                              policy=POLICY, nu_static=0.5,
                                              jitter=1e-6, version="fori")
        return fn, (locs, z, theta)

    def fn(locs, z, obs, new, theta):
        return krige(locs[obs], z[obs], locs[new], theta, POLICY, nb=NB,
                     nu_static=0.5, jitter=1e-6)
    new = jnp.arange(N - NB, N, dtype=jnp.int32)
    obs = jnp.arange(0, N - NB, dtype=jnp.int32)
    return fn, (locs, z, obs, new, theta)


def _no_metadata(hlo: str) -> str:
    """The compiled program without `op_name` metadata and without the
    tables of source locations that the metadata points into."""
    lines = [line for line in hlo.splitlines()
             if line.startswith(("HloModule", "%", "ENTRY", " ", "}"))]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


CASES = [("eval", "square"), ("eval", "chunked"), ("krige", None),
         ("dist", None)]


@pytest.mark.parametrize("which,off_update", CASES)
def test_scopes_change_no_operation(monkeypatch, which, off_update):
    fn, args = _program(which, off_update or "square")
    scoped = jax.jit(fn).lower(*args)
    scoped_hlo = scoped.compile().as_text()
    # JAX would hand back the executable it compiled above for a program
    # that differs from it in metadata alone
    jax.clear_caches()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = jax.jit(fn).lower(*args)
        plain_hlo = plain.compile().as_text()
    # as_text() prints no debug info: locations, where the scopes live, stay out
    assert scoped.as_text() == plain.as_text()
    plain_names = " ".join(re.findall(r'op_name="([^"]*)"', plain_hlo))
    assert "geostat_loglik_step/" not in plain_names
    assert "geostat_loglik_distributed/" not in plain_names
    assert "krige/" not in plain_names
    assert _no_metadata(scoped_hlo) == _no_metadata(plain_hlo)


@pytest.mark.parametrize("which,off_update", CASES)
def test_every_scope_is_in_the_compiled_metadata(which, off_update):
    fn, args = _program(which, off_update or "square")
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in SCOPES[which]:
        assert any(f"/{scope}/" in name for name in op_names), scope


def test_every_phase_of_the_sharded_sweep_is_in_the_compiled_metadata():
    fn, args = _program("dist")
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    factor = "/geostat_loglik_distributed/factor/"
    under = [name.split(factor)[1].split("/") for name in op_names
             if factor in name]
    for phase in DIST_PHASES:
        assert any(phase in parts for parts in under), phase


def test_scope_names_are_fixed_not_per_step():
    fn, args = _program("eval")
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    under = {name.split("/factor/")[1].split("/")[0] for name in op_names
             if "/factor/" in name}
    assert under == set(PANEL_PHASES)


def test_lo_update_kernel_carries_the_update_lo_scope():
    """Lowered for a TPU, the step's lo update is the Pallas kernel, one
    call per step whose trapezoid is not empty (k = 0 alone at p = 4,
    t = 2), and the call's location, which becomes its `op_name`, names
    `geostat_loglik_step/factor/update_lo`.  Lowered for the CPU, the
    same step holds no kernel: the einsum loop."""
    nb = 128
    fn = partial(geostat_loglik_step, nb=nb, policy=POLICY, nu_static=0.5,
                 jitter=1e-6)
    args = (jnp.zeros((4 * nb, 2), jnp.float32),
            jnp.zeros((4 * nb,), jnp.float32), jnp.ones((2,), jnp.float32))
    tpu = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = tpu.as_text(debug_info=True)
    calls = [line for line in text.splitlines()
             if "stablehlo.custom_call @tpu_custom_call" in line]
    assert len(calls) == 1
    loc = re.search(r"loc\((#loc\d+)\)\s*$", calls[0]).group(1)
    name = re.search(rf'^{loc} = loc\("([^"]*)"', text, re.M).group(1)
    assert "/geostat_loglik_step/factor/update_lo/" in name
    assert name.endswith("lo_trailing_update/pallas_call")
    cpu = jax.jit(fn).lower(*args).as_text()
    assert "tpu_custom_call" not in cpu


def test_scoped_step_gives_the_same_answer_eagerly_and_jitted():
    fn, args = _program("eval")
    eager = float(fn(*args))
    assert np.isfinite(eager)
    assert float(jax.jit(fn)(*args)) == pytest.approx(eager, rel=1e-5)
