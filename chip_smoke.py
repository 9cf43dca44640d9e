#!/usr/bin/env python3
"""Drive the geostat main path once on a TPU and check it against fp64.

    python chip_smoke.py              one chip: likelihood, MLE, batched
                                      evaluation and kriging (phases 1-5)
    python chip_smoke.py --chips 4    four chips: the sharded likelihood
                                      (core/distributed.py) against one chip

The field is the paper's synthetic study: a perturbed grid on the unit
square from `covariance.make_dataset` (fixed seed, Morton order), nu = 0.5,
theta = (1, 0.03), the paper's weak-correlation level.  Each phase prints
its lines; the last line of a passing run is one JSON object,
{"ok": true, "device": {...}}.  With no TPU the script exits non-zero
before any phase.  A failed check or a phase that raises exits non-zero.

`--rehearse` runs the same phases at tiny sizes on whatever backend JAX
has (CPU rehearsal before a chip call).  It prints no timings and no
result line.  For four virtual devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python chip_smoke.py --chips 4 --rehearse

Compile cache: JAX reads JAX_COMPILATION_CACHE_DIR when it is set;
otherwise the cache lives at <repo>/.jax_cache.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# the program itself: a copy of this script without the repo fails here
from repro.core import BatchEngine, BatchPlan, PrecisionPolicy  # noqa: E402
from repro.core.distributed import (build_covariance_distributed,  # noqa: E402
                                    geostat_loglik_distributed)
from repro.core.kriging import krige, pmse  # noqa: E402
from repro.core.likelihood import build_covariance  # noqa: E402
from repro.core.mle import fit_mle  # noqa: E402
from repro.core.panel_cholesky import geostat_loglik_step  # noqa: E402
from repro.covariance import make_dataset, matern_covariance  # noqa: E402
from repro.covariance.generator import random_locations  # noqa: E402
from repro.covariance.ordering import morton_order  # noqa: E402
from repro.launch.mesh import make_geostat_mesh  # noqa: E402
from repro.models.sharding import set_activation_mesh  # noqa: E402
from repro.verify.bounds import policy_bound  # noqa: E402
from repro.verify.oracles import (exact_kriging_pmse, exact_loglik,  # noqa: E402
                                  loglik_drift, pmse_drift)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

# (variance, range); nu = 0.5 is pinned (nu_static).  Weak correlation: at
# the medium level (range 0.1) and n >= 8192 the bf16 off-band storage of
# the f32/bf16 pair leaves the covariance indefinite (the factor is NaN)
THETA = (1.0, 0.03)
# Nelder-Mead start, below the range: the first simplex steps the range up
# by e^0.25, and from (0.7, 0.05) (range up to 0.064) an evaluation of
# tpu(4) at n = 32768 was NaN (the f32/bf16 limit of ROADMAP Reach item 10)
START = (0.7, 0.02)
NU = 0.5
SEED = 0
MIXED = PrecisionPolicy.tpu(2)   # any f32/bf16 policy: for the bound lookup
# the four-chip sweep: `fori` compiles one loop body; `masked_full`
# unrolls p full-width steps and compiled 4.7x slower for v5e:2x2 at
# n = 16384 (both fit there)
DIST_VERSION = "fori"


@dataclasses.dataclass(frozen=True)
class Sizes:
    check: tuple = (4096, 512)       # (n, nb) against the fp64 oracle
    full: tuple = (32768, 1024)      # the one-chip field, tpu(4)
    cmp: tuple = (16384, 1024)       # full vs mixed; largest n `full` fits
    batch: tuple = (8192, 512)       # BatchEngine, B = 4, tpu(2)
    krige: tuple = (4096, 512)       # observed n; 10% of the field held out
    mle_iters: int = 4
    dist_big: int = 65536            # four chips; no single chip holds it


CHIP = Sizes()
REHEARSAL = Sizes(check=(256, 32), full=(512, 32), cmp=(256, 32),
                  batch=(256, 32), krige=(256, 32), mle_iters=2,
                  dist_big=512)


class Smoke:
    """Device, sizes and the list of failed checks for one run."""

    def __init__(self, sizes: Sizes, rehearse: bool):
        self.sizes = sizes
        self.rehearse = rehearse
        self.dev = jax.devices()[0]
        self.failures: list[str] = []

    def check(self, name: str, ok: bool, detail: str = "") -> str:
        if not ok:
            self.failures.append(f"{name}: {detail}")
        return "ok" if ok else "FAILED"

    def secs(self, s: float) -> str:
        # a CPU rehearsal's clock is not a device metric
        return "n/a" if self.rehearse else f"{s:.6f}"

    def peak(self, dev=None) -> str:
        stats = (dev or self.dev).memory_stats()
        if self.rehearse or not stats:
            return "n/a"
        return str(stats["peak_bytes_in_use"])


def dataset(n: int):
    """(locs, z) of the synthetic study on the first device.

    Set-up, made on the host CPU: the exact field draw needs a dense
    Cholesky, which XLA:CPU compiles in seconds and the TPU compiler in
    minutes at these n (its compile time grows about 3x per doubling).
    """
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        locs, z = jax.jit(lambda key: make_dataset(
            key, n, jnp.array(THETA + (NU,)), nu_static=NU)[:2])(
                jax.random.PRNGKey(SEED))
    device = jax.devices()[0]
    return jax.device_put(locs, device), jax.device_put(z, device)


# the dense fp32 covariance (jitter as the likelihood adds it), on device
_cov32 = jax.jit(partial(build_covariance, nu_static=NU, jitter=1e-6,
                         dtype=jnp.float32))


def fp64_loglik(locs, z, theta) -> float:
    """Host fp64 oracle on the same fp32 covariance the device builds."""
    return exact_loglik(np.asarray(_cov32(locs, theta)), np.asarray(z))


def timed_program(fn, args):
    """Compile `fn` for `args`, run it once, time 3 warm runs.

    Returns (compiled, ll, compile s, median step s, temp bytes).
    """
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    ll = float(compiled(*args).block_until_ready())
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        compiled(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    ma = compiled.memory_analysis()
    temp = "n/a" if ma is None else str(ma.temp_size_in_bytes)
    return compiled, ll, compile_s, statistics.median(times), temp


def loglik_program(smoke: Smoke, n: int, nb: int, policy, locs, z, theta,
                   label: str):
    """`geostat_loglik_step` on one device: compiled, checked, timed."""
    fn = jax.jit(partial(geostat_loglik_step, nb=nb, policy=policy,
                         nu_static=NU))
    compiled, ll, compile_s, step_s, temp = timed_program(fn, (locs, z, theta))
    smoke.check(f"{label} finite", np.isfinite(ll), f"ll={ll}")
    print(f"  {label} n={n} nb={nb}: ll={ll!r} compile_s="
          f"{smoke.secs(compile_s)} step_s_median3={smoke.secs(step_s)} "
          f"temp_bytes={temp} peak_bytes_in_use={smoke.peak()}", flush=True)
    return compiled, ll


def phase_device(smoke: Smoke, chips: int):
    devs = jax.devices()
    print(f"phase 1 device: platform={smoke.dev.platform} "
          f"kind={smoke.dev.device_kind!r} count={len(devs)} "
          f"jax={jax.__version__} compile_cache="
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    if len(devs) < chips:
        raise SystemExit(f"need {chips} devices, JAX sees {len(devs)}")


def phase_correctness(smoke: Smoke):
    n, nb = smoke.sizes.check
    locs, z = dataset(n)
    theta = jnp.asarray(THETA, jnp.float32)
    ll_ref = fp64_loglik(locs, z, theta)
    mixed_bound = policy_bound(MIXED).loglik_drift
    print(f"phase 2 correctness n={n} nb={nb}: fp64 host oracle "
          f"ll={ll_ref!r}", flush=True)
    for policy, name in ((PrecisionPolicy.full(), "full"),
                         (PrecisionPolicy.tpu(2), "tpu(2)")):
        ll = float(jax.jit(partial(geostat_loglik_step, nb=nb, policy=policy,
                                   nu_static=NU))(locs, z, theta))
        drift = loglik_drift(ll, ll_ref)
        own = policy_bound(policy).loglik_drift
        status = smoke.check(f"{name} drift", np.isfinite(ll)
                             and drift <= mixed_bound,
                             f"drift {drift:.3e} > mixed bound {mixed_bound}")
        print(f"  {name}: ll={ll!r} drift={drift:.3e} own_bound={own:.0e} "
              f"({'within' if drift <= own else 'EXCEEDED'}) "
              f"mixed_bound={mixed_bound:.0e} {status}", flush=True)


def phase_full_width(smoke: Smoke):
    n, nb = smoke.sizes.full
    locs, z = dataset(n)
    theta = jnp.asarray(THETA, jnp.float32)
    print(f"phase 3 full width: tpu(4) at n={n}; full vs tpu(4) at "
          f"n={smoke.sizes.cmp[0]}", flush=True)
    compiled, _ = loglik_program(smoke, n, nb, PrecisionPolicy.tpu(4),
                                 locs, z, theta, "tpu(4)")
    n2, nb2 = smoke.sizes.cmp
    locs2, z2 = dataset(n2)
    _, ll_full = loglik_program(smoke, n2, nb2, PrecisionPolicy.full(),
                                locs2, z2, theta, "full")
    _, ll_mixed = loglik_program(smoke, n2, nb2, PrecisionPolicy.tpu(4),
                                 locs2, z2, theta, "tpu(4)")
    diff = loglik_drift(ll_mixed, ll_full)
    bound = policy_bound(MIXED).loglik_drift
    status = smoke.check("mixed vs full", diff <= bound,
                         f"{diff:.3e} > {bound}")
    print(f"  n={n2} mixed vs full: rel_diff={diff:.3e} "
          f"mixed_bound={bound:.0e} {status}", flush=True)
    return compiled, locs, z


def phase_mle(smoke: Smoke, compiled, locs, z):
    """Nelder-Mead on the phase-3 program (no recompile), then a batch."""
    iters = smoke.sizes.mle_iters
    evals = []    # (theta, ll) as computed, before fit_mle maps NaN to 1e10

    def loglik(th):
        evals.append((np.asarray(th).tolist(), float(compiled(locs, z, th))))
        return evals[-1][1]

    res = fit_mle(loglik, START, max_iters=iters, jit=False)
    start_ll = evals[0][1]                # the likelihood at START
    bad = [np.round(th, 4).tolist() for th, ll in evals
           if not np.isfinite(ll)]
    status = smoke.check("mle", not bad and res.loglik > start_ll,
                         f"non-finite at theta {bad}; "
                         f"ll {res.loglik} vs start {start_ll}")
    print(f"phase 4 mle n={locs.shape[0]}: {iters} Nelder-Mead iterations "
          f"from {START}, evals={res.n_evals} non_finite_at={bad} theta_hat="
          f"{np.round(res.theta, 6).tolist()} ll={float(res.loglik)!r} "
          f"(start ll={start_ll!r}) {status}", flush=True)

    n, nb = smoke.sizes.batch
    locs_b, z_b = dataset(n)
    engine = BatchEngine(locs_b, z_b, BatchPlan(PrecisionPolicy.tpu(2), nb=nb,
                                                path="panel", nu_static=NU))
    cands = jnp.asarray([THETA, (0.7, 0.05), (1.3, 0.02), (1.0, 0.06)],
                        jnp.float32)
    t0 = time.perf_counter()
    lls = np.asarray(engine.loglik(cands))
    first_s = time.perf_counter() - t0
    bound = policy_bound(MIXED).loglik_drift
    drifts = [loglik_drift(ll, fp64_loglik(locs_b, z_b, c))
              for ll, c in zip(lls, cands)]
    status = smoke.check("batch", bool(np.all(np.isfinite(lls)))
                         and max(drifts) <= bound,
                         f"drifts {drifts} vs bound {bound}")
    print(f"  BatchEngine(panel, tpu(2)) n={n} nb={nb} B={len(cands)}: "
          f"ll={lls.tolist()} max_drift_vs_fp64={max(drifts):.3e} "
          f"bound={bound:.0e} first_call_s={smoke.secs(first_s)} {status}",
          flush=True)


def phase_kriging(smoke: Smoke):
    n_obs, nb = smoke.sizes.krige
    m = round(n_obs / 9)                  # 10% of the n_obs + m field
    locs, z = dataset(n_obs + m)
    rng = np.random.default_rng(SEED)
    new = np.sort(rng.choice(n_obs + m, size=m, replace=False))
    obs = np.setdiff1d(np.arange(n_obs + m), new)   # keeps Morton order
    theta = jnp.asarray(THETA, jnp.float32)
    policy = PrecisionPolicy.tpu(2)
    mu = jax.jit(partial(krige, policy=policy, nb=nb, nu_static=NU))(
        locs[obs], z[obs], locs[new], theta)
    got = float(pmse(mu, z[new]))
    cov = _cov32(locs[obs], theta)
    sigma_no = jax.jit(partial(matern_covariance, nu_static=NU))(
        locs[new], locs[obs], theta)
    ref = exact_kriging_pmse(np.asarray(cov), np.asarray(z[obs]),
                             np.asarray(sigma_no), np.asarray(z[new]))
    drift = pmse_drift(got, ref)
    bound = policy_bound(policy).pmse_rel
    status = smoke.check("kriging", np.isfinite(got) and drift <= bound,
                         f"pmse drift {drift:.3e} > {bound}")
    print(f"phase 5 kriging tpu(2) n_obs={n_obs} held_out={m} nb={nb}: "
          f"pmse={got!r} fp64_pmse={ref!r} drift={drift:.3e} "
          f"bound={bound:.0e} {status}", flush=True)


def phase_four_chips(smoke: Smoke):
    """Sharded likelihood on a 2x2 Auto mesh vs one chip, then a field no
    single chip holds."""
    devs = jax.devices()[:4]
    mesh = make_geostat_mesh(devs)
    set_activation_mesh(mesh)
    rows = NamedSharding(mesh, P("data", None))
    vec = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    policy = PrecisionPolicy.tpu(4)
    n, nb = smoke.sizes.cmp
    theta = jnp.asarray(THETA, jnp.float32)
    dist = jax.jit(partial(geostat_loglik_distributed, nb=nb, policy=policy,
                           nu_static=NU, version=DIST_VERSION))
    print(f"phase 2 four chips: mesh={dict(mesh.shape)} version="
          f"{DIST_VERSION} tpu(4) nb={nb}", flush=True)

    locs, z = dataset(n)                      # on device 0
    _, ll_one = loglik_program(smoke, n, nb, policy, locs, z, theta,
                               "one chip (device 0)")
    args = (jax.device_put(locs, rows), jax.device_put(z, vec),
            jax.device_put(theta, rep))
    ll_dist = _dist_program(smoke, dist, args, n, "four chips")
    diff = loglik_drift(ll_dist, ll_one)
    bound = policy_bound(MIXED).loglik_drift
    status = smoke.check("four vs one chip", diff <= bound,
                         f"{diff:.3e} > {bound}")
    print(f"  n={n} four chips vs one chip: rel_diff={diff:.3e} "
          f"mixed_bound={bound:.0e} {status}", flush=True)

    # n beyond one chip: locations from the study's generator, Morton
    # ordered, built sharded; an exact field draw would itself need the
    # n-point factorization, so z is white noise (checked: finite only)
    big = smoke.sizes.dist_big
    locs_big = jax.jit(lambda k: _ordered_locations(k, big),
                       out_shardings=rows)(jax.random.PRNGKey(SEED))
    z_big = jax.jit(lambda k: jax.random.normal(k, (big,)),
                    out_shardings=vec)(jax.random.PRNGKey(SEED + 1))
    off, band = jax.jit(partial(build_covariance_distributed, nb=nb,
                                policy=policy, nu_static=NU))(
        locs_big, args[2])
    shard_devs = {s.device for s in off.addressable_shards}
    shard_bytes = sorted({s.data.nbytes for s in off.addressable_shards})
    split = (len(shard_devs) == len(devs)
             and shard_bytes == [off.nbytes // len(devs)])
    status = smoke.check("off split", split,
                         f"off shards on {len(shard_devs)} devices, "
                         f"bytes {shard_bytes} of {off.nbytes}")
    print(f"  n={big} off {off.shape} {off.dtype} total_bytes={off.nbytes} "
          f"sharding={off.sharding.spec} per_device_bytes="
          f"{[s.data.nbytes for s in off.addressable_shards]} on "
          f"{len(shard_devs)} devices {status}", flush=True)
    del off, band
    _dist_program(smoke, dist, (locs_big, z_big, args[2]), big,
                  "four chips")
    print("  per-device peak_bytes_in_use: "
          f"{[smoke.peak(d) for d in devs]}", flush=True)


def _ordered_locations(key, n):
    locs = random_locations(key, n)
    return locs[morton_order(locs)]


def _dist_program(smoke: Smoke, dist, args, n: int, label: str) -> float:
    _, ll, compile_s, step_s, temp = timed_program(dist, args)
    smoke.check(f"{label} n={n} finite", np.isfinite(ll), f"ll={ll}")
    print(f"  {label} n={n}: ll={ll!r} compile_s={smoke.secs(compile_s)} "
          f"step_s_median3={smoke.secs(step_s)} "
          f"temp_bytes_per_device={temp}", flush=True)
    return ll


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no result")
    args = ap.parse_args(argv)

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    smoke = Smoke(REHEARSAL if args.rehearse else CHIP, args.rehearse)
    if smoke.dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: JAX's first device is {smoke.dev.platform!r}; "
              "this check runs on the chip only", file=sys.stderr)
        return 2
    phase_device(smoke, args.chips)
    if args.chips == 4:
        phase_four_chips(smoke)
    else:
        phase_correctness(smoke)
        compiled, locs, z = phase_full_width(smoke)
        phase_mle(smoke, compiled, locs, z)
        phase_kriging(smoke)

    if smoke.failures:
        print("FAILED checks:\n  " + "\n  ".join(smoke.failures),
              file=sys.stderr)
        return 1
    if args.rehearse:
        print(f"rehearsal passed on {smoke.dev.platform} x "
              f"{len(jax.devices())} (tiny sizes; not a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": smoke.dev.platform, "kind": smoke.dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
