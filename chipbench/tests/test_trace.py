"""The trace reduction, on hand-made planes and on a trace from the chip."""

from pathlib import Path

import pytest

from chipbench import trace

FIXTURE = Path(__file__).parent / "data" / "evals_tiny.xplane.pb"


def _planes(ops, modules, spans):
    return [("/host:CPU", [("python3", spans)]),
            ("/device:TPU:0", [(trace.OPS_LINE, ops),
                               (trace.MODULES_LINE, modules)])]


def test_busy_union_clips_to_window_and_merges_overlaps():
    spans = [("cb.window", 100, 1000), ("cb.eval", 150, 300),
             ("cb.eval", 600, 300)]
    ops = [("fusion.1", 50, 100),        # 100..150 inside the window
           ("fusion.2", 200, 100),       # 200..300
           ("dot.3", 250, 100),          # overlaps: union 200..350
           ("fusion.4", 1050, 200)]      # 1050..1100 inside
    mods = [("jit_cb_eval(7)", 150, 250), ("jit_cb_eval(7)", 600, 300)]
    red = trace.reduce_planes(_planes(ops, mods, spans))
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx((50 + 150 + 50) * 1e-9)
    assert red.idle_share == pytest.approx(0.75)
    assert red.module_ms("cb_eval") == pytest.approx(275e-6)
    assert red.module_ms("cb_factor") is None
    assert red.ops["fusion"] == pytest.approx(200e-9)
    assert red.ops["dot"] == pytest.approx(100e-9)


def test_gaps_are_labelled_by_the_innermost_host_span():
    spans = [("cb.window", 0, 1000), ("cb.fit", 0, 990),
             ("cb.eval", 0, 400), ("cb.eval", 700, 290)]
    ops = [("fusion.1", 0, 300), ("fusion.1", 720, 200)]
    red = trace.reduce_planes(_planes(ops, [], spans))
    got = [(label, round(s * 1e9)) for label, s in red.gaps]
    # 300..720: host in the fit's own loop; 920..1000: the second eval's
    # sync (920..990), labelled by the span open at the gap's middle
    assert got == [("cb.fit", 420), ("cb.eval", 80)]
    assert red.breakdown()["idle_gaps"][0] == ["cb.fit", pytest.approx(420e-9)]


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError, match="cb.window"):
        trace.reduce_planes(_planes([], [], []))
    with pytest.raises(ValueError, match="device"):
        trace.reduce_planes([("/host:CPU", [("t", [("cb.window", 0, 9)])])])


def test_chip_trace_of_the_evals_traffic():
    """Recorded on a v5e by tests/record_trace.py (rehearsal sizes): every
    traced step is one `jit_cb_eval` module on the device; the device is
    busy for part of the window."""
    planes = trace.load(FIXTURE)
    red = trace.reduce_planes(planes)
    assert red.chips == 1
    assert 0 < red.busy_s < red.window_s
    evals = sum(1 for name, lines in planes if name.startswith("/host")
                for _, events in lines for e in events if e[0] == "cb.eval")
    # device and host clocks of this trace differ by about 1 ms, more than
    # one tiny step: the first execution may fall before the window
    assert evals - 2 <= red.executions("cb_eval") <= evals
    assert red.module_ms("cb_eval") > 0
    assert red.breakdown()["device_ops"]
    assert all(s > 0 for _, s in red.gaps)
