"""The four-chip cell: its draw and reference, its per-chip trace readers,
its faults, and its program's scopes."""

import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import (data, draw_reference, per_chip, peaks, program_sharded,
                       reference, run, scopes, spec, trace)

CELL = "synth64k.evals.4chip"
ROOT = spec.ROOT


# ---------------------------------------------------------------------------
# the draw and its reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [3, 2 ** 33 + 5])
def test_draw_is_the_field_and_its_exact_loglik(seed):
    """The sites and field of `data.field` bit for bit, and the
    log-likelihood `reference.loglik` computes by factoring again."""
    theta = data.box_draw(data.rng(seed, 3), [[0.7, 1.3], [0.02, 0.03]], 1)[0]
    locs, z, ll_ref = draw_reference.field_and_loglik(seed, 0, 512, theta,
                                                      0.5, 1e-6)
    locs_f, z_f = data.field(seed, 0, 512, theta, 0.5, 1e-6)
    assert locs.dtype == np.float32 and z.dtype == np.float32
    np.testing.assert_array_equal(locs, locs_f)
    np.testing.assert_array_equal(z, z_f)
    want = reference.loglik(locs_f, z_f, theta, nu=0.5, jitter=1e-6)
    assert ll_ref == pytest.approx(want, rel=1e-12)


# ---------------------------------------------------------------------------
# per-chip readers on four device planes
# ---------------------------------------------------------------------------

def _four_chips(ops, modules, spans):
    return [("/host:CPU", [("python3", spans)])] + [
        (f"/device:TPU:{i}", [(trace.OPS_LINE, ops),
                              (trace.MODULES_LINE, modules)])
        for i in range(4)]


SPANS = [("cb.window", 0, 1000), ("cb.eval", 0, 500), ("cb.eval", 500, 500)]
OPS = [("fusion.1", 0, 300),
       ("all-gather.4", 300, 50),
       ("collective-permute-start.2", 350, 10),
       ("collective-permute-done.2", 360, 30),
       ("async-collective-start", 390, 10),
       ("fusion.1", 500, 300),
       ("all-reduce.7", 800, 100)]
MODULES = [("jit_cb_eval4(3)", 0, 400), ("jit_cb_eval4(3)", 500, 400)]


def _rctx(n=65536):
    red = trace.reduce_planes(_four_chips(OPS, MODULES, SPANS))
    return SimpleNamespace(trace=red, counters={}, ctx=SimpleNamespace(n=n),
                           peak=peaks.peak("TPU v5 lite"))


def test_four_planes_count_each_call_once_per_chip():
    rctx = _rctx()
    assert rctx.trace.chips == 4
    assert rctx.trace.executions("cb_eval4") == 8
    assert per_chip.calls(rctx, "cb_eval4") == 2


def test_collectives_are_per_chip_and_per_evaluation():
    # one chip: 50 + 10 + 30 + 10 + 100 ns of collectives in two calls
    got = spec.reader("dist_collective_ms")(_rctx())
    assert got == pytest.approx(200e-9 / 2 * 1e3)


def test_collectives_in_flight_do_not_count():
    """An asynchronous collective's span on the `Async XLA Ops` line runs
    beside compute; the reading keeps to the synchronous line."""
    planes = _four_chips(OPS, MODULES, SPANS)
    planes[1][1].append(("Async XLA Ops",
                         [("collective-permute-start.2", 350, 600)]))
    rctx = SimpleNamespace(trace=trace.reduce_planes(planes))
    assert spec.reader("dist_collective_ms")(rctx) == \
        pytest.approx(200e-9 / 2 * 1e3)


def test_idle_share_is_the_mean_over_chips():
    # every chip busy 0..400 and 500..900 of the 1000 ns window
    assert spec.reader("device_idle_pct.eval")(_rctx()) == \
        pytest.approx(20.0)


def test_idle_share_is_the_mean_of_unequal_chips():
    """Chip 0 busy 0..400 and 500..900, the others 0..100: the mean of
    10% and three times 90% idle, not the idle of the union."""
    planes = _four_chips(OPS, MODULES, SPANS)
    for i in (1, 2, 3):
        planes[1 + i] = (f"/device:TPU:{i}",
                         [(trace.OPS_LINE, [("fusion.1", 0, 100)]),
                          (trace.MODULES_LINE, MODULES)])
    rctx = SimpleNamespace(trace=trace.reduce_planes(planes))
    assert spec.reader("device_idle_pct.eval")(rctx) == \
        pytest.approx((20.0 + 3 * 90.0) / 4)


def test_step_share_of_peak_is_per_chip():
    """Two evaluations a microsecond over four chips' peak, not eight."""
    rctx = _rctx(n=4096)
    from chipbench import flops
    want = 100.0 * flops.loglik_flops(4096) * 2 / 1e-6 \
        / (4 * peaks.peak("TPU v5 lite")["bf16_flops_per_s"])
    assert spec.reader("eval_mfu_pct.4chip")(rctx) == pytest.approx(want)


def test_readers_find_nothing_without_the_program():
    rctx = _rctx()
    rctx.trace.modules.clear()
    for m in ("eval_mfu_pct.4chip", "dist_collective_ms", "dist_factor_ms",
              "dist_factor_panel_ms", "dist_factor_update_hi_ms",
              "dist_factor_update_lo_ms"):
        assert spec.reader(m)(rctx) is None


ROOT_PATH = "geostat_loglik_distributed"
BODY = f"{ROOT_PATH}/factor/while/body/closed_call"
SECONDS = {"cb_eval4": {f"{ROOT_PATH}/cov_build": 8e-6,
                        f"{ROOT_PATH}/factor": 4e-6,
                        f"{BODY}/potrf": 4e-6,
                        f"{BODY}/trsm_hi": 16e-6,
                        f"{BODY}/trsm_lo": 2e-6,
                        f"{BODY}/gather": 1e-6,
                        f"{BODY}/update_hi": 6e-6,
                        f"{BODY}/update_hi/iab,icb->iac": 12e-6,
                        f"{BODY}/update_lo": 40e-6,
                        f"{BODY}/update_lo/x": 4e-6,
                        f"{ROOT_PATH}/solve": 2e-6,
                        scopes.NO_SCOPE: 1e-6}}


@pytest.mark.parametrize("name,want_s", [
    ("dist_cov_build_ms", 8e-6),
    ("dist_factor_ms", 89e-6),
    ("dist_factor_panel_ms", 22e-6),
    ("dist_factor_update_hi_ms", 18e-6),
    ("dist_factor_update_lo_ms", 44e-6),
    ("dist_solve_ms", 2e-6)])
def test_scope_readers_are_per_chip_and_find_phases_inside_the_loop(
        monkeypatch, name, want_s):
    """`scopes` averages seconds over the chips already; the readers divide
    by the calls per chip, and find a phase under the loop's body."""
    monkeypatch.setattr(scopes, "traced_file", lambda: "t.xplane.pb")
    monkeypatch.setattr(scopes, "reduce", lambda path: scopes.Scopes(
        window_s=1e-6, seconds=SECONDS))
    assert spec.reader(name)(_rctx()) == pytest.approx(want_s / 2 * 1e3)


# ---------------------------------------------------------------------------
# the cell at its rehearsal size
# ---------------------------------------------------------------------------

def _run(seconds=2.0):
    return run.run_cell(spec.cell(CELL), seed=4242, seconds=seconds,
                        traced=False, rehearse=True, counters=run.Counters(),
                        log=lambda *a, **k: None)


def test_altered_sharded_likelihood_is_not_correct(monkeypatch):
    """3% off: three times the cell's `ll_drift` limit (1e-2, set above
    the program's 2.9e-3 at the box's corner on the chip; PERF.md)."""
    real = program_sharded.geostat_loglik_distributed
    monkeypatch.setattr(program_sharded, "geostat_loglik_distributed",
                        lambda *a, **k: real(*a, **k) * 1.03)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["ll_drift"]["value"] > \
        res["checks"]["ll_drift"]["limit"]


def test_nonfinite_sharded_answer_is_not_correct(monkeypatch):
    real = program_sharded.geostat_loglik_distributed
    monkeypatch.setattr(program_sharded, "geostat_loglik_distributed",
                        lambda *a, **k: real(*a, **k) * np.nan)
    res = _run(seconds=0.2)
    assert not res["correct"]
    assert res["checks"]["nonfinite"]["value"] == res["attempted"] > 0


def test_every_fourth_call_of_the_cycle_is_at_the_drawn_theta():
    from chipbench import drivers

    cell = spec.cell(CELL)
    ctx = drivers.Ctx.make(cell.config, cell.traffic, 11, rehearse=True)
    drv = drivers.driver(ctx, 4)
    drv.setup()
    thetas = np.array([np.asarray(th) for _, _, th in drv.thetas])
    drv.free()
    star = (np.arange(len(thetas)) % cell.traffic["theta_star_every"]) == 0
    assert np.all(thetas[star] == drv.theta_star)
    assert not np.any(np.all(thetas[~star] == drv.theta_star, axis=1))
    box = np.asarray(cell.traffic["theta_box"])
    assert np.all((thetas >= box[:, 0]) & (thetas <= box[:, 1]))
    drv.lls = np.zeros(9)
    assert drv.at_theta_star().tolist() == [0, 4, 8]


def test_compiled_program_names_its_phases_on_the_cpu():
    """The HLO proto the profiler puts in the trace: the Cholesky of each
    step under the loop body's `potrf`, the lo GEMM under `update_lo`."""
    import jax

    from chipbench import drivers
    from chipbench.kinds.evals_sharded import eval_fn

    cell = spec.cell(CELL)
    ctx = drivers.Ctx.make(cell.config, cell.traffic, 1, rehearse=True)
    args = (jax.numpy.zeros((ctx.n, 2)), jax.numpy.zeros(ctx.n),
            jax.numpy.ones(2))
    exe = jax.jit(eval_fn(ctx)).lower(*args).compile()
    module = scopes.schema()["HloModuleProto"]()
    module.ParseFromString(exe.runtime_executable().hlo_modules()[0]
                           .as_serialized_hlo_module_proto())
    assert module.name == "jit_cb_eval4"
    got = scopes.scope_map(module)
    factor = f"{ROOT_PATH}/factor/"
    chol = [v for k, v in got.items() if k.startswith("cholesky")]
    dots = [v for k, v in got.items() if k.startswith("dot")]
    assert chol and all(p.startswith(factor) and p.endswith("/potrf")
                        for p in chol)
    assert any(p.startswith(factor) and "update_lo" in p.split("/")
               for p in dots)


def test_rehearsal_on_four_host_devices():
    """The 2x2 mesh path of the kind, on four virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--rehearse", "--workload", CELL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert f"rehearsed {CELL}: attempted=" in proc.stdout
    assert "failed=0" in proc.stdout
