"""With the timed path broken underneath, a run's `correct` is false.

The harness runs as on the chip but at the rehearsal size on the CPU,
with the program's entry replaced by a faulty one: an answer altered
where it is produced (every cell), half of the held-out sites left out
of a kriging answer (the predict cell).  The control -- the program
with its far tier in fp8 -- is kept here at a size the CPU holds, where
it already reads several times the program's gap.
"""

import numpy as np
import pytest

from chipbench import drivers, program, run, spec

SEED = 4242


def _run(name, seconds=2.0):
    return run.run_cell(spec.cell(name), seed=SEED, seconds=seconds,
                        traced=False, rehearse=True, counters=run.Counters(),
                        log=lambda *a, **k: None)


def _altered_loglik(real):
    def step(*args, **kw):
        return real(*args, **kw) * 1.01
    return step


@pytest.mark.parametrize("name", ["synth32k.evals", "synth8k.fits"])
def test_altered_likelihood_is_not_correct(monkeypatch, name):
    assert _run(name)["correct"]
    monkeypatch.setattr(program, "geostat_loglik_step",
                        _altered_loglik(program.geostat_loglik_step))
    res = _run(name)
    assert not res["correct"]
    assert res["checks"]["ll_drift"]["value"] > \
        res["checks"]["ll_drift"]["limit"]


@pytest.mark.parametrize("fault", ["altered", "half_left_out"])
def test_broken_kriging_is_not_correct(monkeypatch, fault):
    real = program.krige

    def krige(*args, **kw):
        mu = real(*args, **kw)
        if fault == "altered":
            return mu * 1.05
        return mu.at[: mu.shape[0] // 2].set(0.0)

    monkeypatch.setattr(program, "krige", krige)
    res = _run("synth8k.predict")
    assert not res["correct"]


def test_nonfinite_answer_is_not_correct(monkeypatch):
    real = program.geostat_loglik_step
    monkeypatch.setattr(program, "geostat_loglik_step",
                        lambda *a, **k: real(*a, **k) * np.nan)
    res = _run("synth32k.evals", seconds=0.2)
    assert not res["correct"]
    assert res["checks"]["nonfinite"]["value"] == res["attempted"] > 0


@pytest.mark.parametrize("seed", [5, 6])
def test_control_reads_far_above_the_program(seed):
    """n = 1024, nb = 128, t = 2, the largest gap over four thetas: the
    control's is three times the program's or more (on the chip at the
    cells' own sizes: PERF.md)."""
    cell = spec.cell("synth8k.fits")
    config = dict(cell.config, rehearse={"n": 1024, "nb": 128, "diag_thick": 2})
    traffic = dict(spec.cell("synth32k.evals").traffic, check_sample=4)
    readings = []
    for control in (False, True):
        ctx = drivers.Ctx.make(config, traffic, seed, rehearse=True,
                               control=control)
        drv = drivers.driver(ctx)
        drv.setup()
        drv.window(0.3)
        drv.free()
        readings.append(drv.checks()["ll_drift"][0])
    program_gap, control_gap = readings
    assert control_gap > 3 * program_gap
