"""The sharded `fori` likelihood on a 2x2 mesh of four host devices against
the fp64 reference and the one-chip step, and its factor against the
unrolled `masked_full` sweep's.

XLA's CPU backend gives four devices only when told so before JAX starts,
so the programs run in a child process
(`--xla_force_host_platform_device_count=4`) and hand back their
readings.  The field: n = 512, nb = 32, a band of two tiles, nu = 0.5 at
the medium level theta = (1, 0.1).  At the weak level's range (0.03) the
sites of a 512-point field lie farther apart than the range, the
off-band tiles hold almost nothing, and the fp8 far tier reads within
3x of bfloat16 there, so no tolerance could tell the two apart.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SEEDS = (0, 1, 2)

# |ll - ll_ref| / |ll_ref| against the fp64 reference: the sharded program
# read 2.65e-5 to 6.95e-4 on the three fields (the one-chip step 9.4e-5
# to 5.7e-4), its fp8 far tier 5.4e-3 to 1.5e-2.  2e-3 leaves about 3x
# on each side.
TOL_REF = 2e-3
# |ll - ll_step| / |ll_ref| against the one-chip step in the same
# precision: the same tiers, summed in another order (full-width masked
# updates, column-wise solve), read 4.0e-5 to 1.23e-4; the fp8 far tier
# sits 5e-3 or more away.  5e-4 is 4x the largest.
TOL_STEP = 5e-4

# max |x_fori - x_unrolled| / max |x_unrolled|, for the factored band and
# off: both sweeps read 0 on the three fields (the same operations on the
# same tiles); one rounding of the bf16 off tiles moved the wrong way
# would read about 4e-3.
TOL_FACTOR = 1e-6

CHILD = r"""
import json, sys
from functools import partial
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import PrecisionPolicy
from repro.core.distributed import (build_covariance_distributed,
                                    geostat_loglik_distributed,
                                    panel_cholesky_distributed)
from repro.core.panel_cholesky import geostat_loglik_step
from repro.covariance import make_dataset
from repro.launch.mesh import make_geostat_mesh
from repro.models.sharding import set_activation_mesh
from repro.verify.oracles import exact_loglik

N, NB, T, THETA, JITTER = 512, 32, 2, (1.0, 0.1), 1e-6
devs = jax.devices()
mesh = make_geostat_mesh(devs[:4])
rows, vec, rep = (NamedSharding(mesh, P("data", None)),
                  NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))
theta = jnp.asarray(THETA, jnp.float32)


def policy(lo):
    return PrecisionPolicy(mode="mixed", hi=jnp.float32, lo=jnp.dtype(lo),
                           diag_thick=T, solve_dtype=jnp.float32,
                           accum_dtype=jnp.float32)


def sharded(lo):
    set_activation_mesh(mesh)
    try:
        fn = jax.jit(partial(geostat_loglik_distributed, nb=NB,
                             policy=policy(lo), nu_static=0.5, jitter=JITTER,
                             version="fori"))
        args = (jax.ShapeDtypeStruct((N, 2), jnp.float32, sharding=rows),
                jax.ShapeDtypeStruct((N,), jnp.float32, sharding=vec),
                jax.ShapeDtypeStruct((2,), jnp.float32, sharding=rep))
        return fn.lower(*args).compile()
    finally:
        set_activation_mesh(None)


def factored(version):
    def fn(locs, theta):
        off, band = build_covariance_distributed(
            locs, theta, nb=NB, policy=policy("bfloat16"), nu_static=0.5,
            jitter=JITTER, version=version)
        return panel_cholesky_distributed(off, band, policy("bfloat16"),
                                          version=version)
    set_activation_mesh(mesh)
    try:
        return jax.jit(fn).lower(
            jax.ShapeDtypeStruct((N, 2), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((2,), jnp.float32, sharding=rep)).compile()
    finally:
        set_activation_mesh(None)


def gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


programs = {lo: sharded(lo) for lo in ("bfloat16", "float8_e4m3fn")}
factors = {v: factored(v) for v in ("fori", "masked_full")}
step = jax.jit(partial(geostat_loglik_step, nb=NB, policy=policy("bfloat16"),
                       nu_static=0.5, jitter=JITTER))
out = {"devices": len(devs),
       "collectives": sum(programs["bfloat16"].as_text().count(c) for c in
                          ("all-gather", "all-reduce", "collective-permute",
                           "all-to-all", "reduce-scatter")),
       "fields": []}
for seed in json.loads(sys.argv[1]):
    ds = make_dataset(jax.random.PRNGKey(seed), N, [*THETA, 0.5],
                      nu_static=0.5)
    locs = np.asarray(ds.locs, np.float64)
    r = np.sqrt(((locs[:, None] - locs[None]) ** 2).sum(-1))
    cov = THETA[0] * np.exp(-r / float(theta[1])) + JITTER * np.eye(N)
    args = (jax.device_put(ds.locs, rows), jax.device_put(ds.z, vec),
            jax.device_put(theta, rep))
    (off, band), (off_u, band_u) = (factors[v](args[0], args[2])
                                    for v in ("fori", "masked_full"))
    out["fields"].append({
        "seed": seed, "ref": exact_loglik(cov, np.asarray(ds.z)),
        "step": float(step(ds.locs, ds.z, theta)),
        "factor_gap": {"off": gap(off, np.asarray(off_u).T),  # fori: L^T
                       "band": gap(band, band_u)},
        **{lo: float(exe(*args)) for lo, exe in programs.items()}})
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", CHILD, json.dumps(SEEDS)],
                          env=env, cwd=ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    out["fields"] = {f["seed"]: f for f in out["fields"]}
    return out


def _rel(a, b, ref):
    return abs(a - b) / abs(ref)


def test_the_program_is_partitioned_over_four_devices(readings):
    assert readings["devices"] == 4
    assert readings["collectives"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_fori_matches_the_fp64_reference(readings, seed):
    f = readings["fields"][seed]
    assert _rel(f["bfloat16"], f["ref"], f["ref"]) <= TOL_REF


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_fori_matches_the_one_chip_step(readings, seed):
    f = readings["fields"][seed]
    assert _rel(f["bfloat16"], f["step"], f["ref"]) <= TOL_STEP


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_fori_factors_as_the_unrolled_sweep(readings, seed):
    """The `fori` body reads and writes the band's tiles at the traced
    step, in its own layout (L^T, each tile's rows split over the mesh);
    the unrolled `masked_full` sweep, which shares no code with that
    body, slices them statically from L's storage.  Both factor the same
    tiles in the same precisions, so the factored band and off-band
    storage agree to within f32 rounding."""
    gaps = readings["fields"][seed]["factor_gap"]
    assert gaps["band"] <= TOL_FACTOR and gaps["off"] <= TOL_FACTOR


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fp8_far_tier_fails_both_tolerances(readings, seed):
    f = readings["fields"][seed]
    assert _rel(f["float8_e4m3fn"], f["ref"], f["ref"]) > TOL_REF
    assert _rel(f["float8_e4m3fn"], f["step"], f["ref"]) > TOL_STEP
