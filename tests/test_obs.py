"""Telemetry layer tests (repro.obs): recorder semantics, thread safety,
exporter round-trips, the merged Chrome trace, kernel-time calibration, and
the disabled-mode overhead guard.
"""

import contextlib
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.precision import PrecisionPolicy
from repro.core.tile_cholesky import tile_cholesky
from repro.launch.costmodel import (
    load_calibration,
    set_calibration,
    task_virtual_cost,
)
from repro.obs.calibrate import cost_key, measure_kernel_times, write_calibration
from repro.sched.config import SchedConfig
from repro.sched.runtime import build_graph, scheduled_tile_cholesky, simulate
from repro.sched.trace import validate_trace
from repro.verify.generators import spd_matrix

POLICY = PrecisionPolicy.tpu(2)


# ---------------------------------------------------------------------------
# recorder: counters / gauges / histograms
# ---------------------------------------------------------------------------

def test_counters_and_gauges():
    rec = obs.Recorder()
    rec.inc("a")
    rec.inc("a", 2)
    rec.gauge("g", 3.5)
    rec.gauge("g", 4.5)          # gauges overwrite
    snap = rec.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 4.5


def test_histogram_bucket_edges_le_semantics():
    h = obs.Histogram(edges=(1.0, 2.0, 4.0))
    # Prometheus `le`: a value equal to an edge lands IN that bucket
    for v in (0.5, 1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v)
    assert h.counts == [2, 1, 2, 1]      # (<=1, <=2, <=4, +Inf overflow)
    assert h.count == 6
    assert h.min == 0.5 and h.max == 5.0
    assert h.total == pytest.approx(15.5)
    # bucket_rows are cumulative; the +Inf row equals the total count
    assert h.bucket_rows() == [(1.0, 2), (2.0, 3), (4.0, 5),
                               (float("inf"), 6)]


def test_histogram_rejects_unsorted_edges():
    with pytest.raises(ValueError):
        obs.Histogram(edges=(2.0, 1.0))


def test_observe_uses_default_buckets():
    rec = obs.Recorder()
    rec.observe("h", 0.5)
    h = rec.histograms["h"]
    assert tuple(h.edges) == obs.recorder.DEFAULT_BUCKETS


# ---------------------------------------------------------------------------
# spans: nesting, exception unwinding
# ---------------------------------------------------------------------------

def test_span_nesting_depths():
    rec = obs.Recorder()
    with rec.span("outer"):
        with rec.span("inner"):
            pass
        with rec.span("inner2"):
            pass
    by_name = {s.name: s for s in rec.spans}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["inner2"].depth == 1
    # children recorded before the parent closes
    assert [s.name for s in rec.spans] == ["inner", "inner2", "outer"]
    # span durations also feed a histogram of the same name
    assert rec.histograms["outer"].count == 1


def test_span_exception_unwinds_and_propagates():
    rec = obs.Recorder()
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            raise RuntimeError("x")
    (s,) = rec.spans
    assert s.status == "error"
    # depth stack unwound: a fresh span is a root again
    with rec.span("after"):
        pass
    assert rec.spans[-1].depth == 0


def test_span_attrs_recorded():
    rec = obs.Recorder()
    with rec.span("s", n=128, mode="mixed"):
        pass
    assert rec.spans[0].attrs == {"n": 128, "mode": "mixed"}


# ---------------------------------------------------------------------------
# global switch / maybe_span
# ---------------------------------------------------------------------------

def test_disabled_module_helpers_are_noops():
    assert not obs.enabled()
    assert obs.span("x") is obs.NULL_SPAN
    assert obs.maybe_span("x", jnp.zeros(1)) is obs.NULL_SPAN
    before = obs.get_recorder().snapshot()
    obs.inc("c")
    obs.observe("h", 1.0)
    obs.gauge("g", 1.0)
    assert obs.get_recorder().snapshot() == before


def test_recording_restores_previous_state():
    assert not obs.enabled()
    with obs.recording() as rec:
        assert obs.enabled()
        assert obs.get_recorder() is rec
        obs.inc("c")
    assert not obs.enabled()
    assert rec.counters["c"] == 1


def test_maybe_span_noops_under_jit():
    a = np.asarray(spd_matrix(3, 64, cond=10.0))
    with obs.recording() as rec:
        tile_cholesky(jnp.asarray(a), 32, POLICY)            # eager: records
        jax.jit(lambda x: tile_cholesky(x, 32, POLICY))(
            jnp.asarray(a)).block_until_ready()              # traced: no-op
    names = [s.name for s in rec.spans]
    assert names.count("core.tile_cholesky") == 1


# ---------------------------------------------------------------------------
# program spans on the profiler's clock; the MLE loop's spans
# ---------------------------------------------------------------------------

def _profiled_host_events(tmp_path, body):
    """Names of the host events a CPU profiler trace holds around `body`."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return {ev.name for plane in data.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("enabled", [True, False])
def test_span_writes_a_profiler_host_event_only_when_enabled(tmp_path,
                                                             enabled):
    def body():
        with obs.recording() if enabled else contextlib.nullcontext():
            with obs.span("test.annotated"):
                jnp.ones(4).block_until_ready()

    names = _profiled_host_events(tmp_path, body)
    assert ("test.annotated" in names) == enabled


def _peaked(theta):
    """A log-likelihood peaked at theta = (1.5, 0.2), for the optimizer."""
    t = jnp.log(theta) - jnp.log(jnp.array([1.5, 0.2]))
    return -jnp.sum(t * t)


def test_mle_spans_count_iterations_and_evaluations():
    from repro.core.mle import fit_mle

    with obs.recording() as rec:
        res = fit_mle(_peaked, [1.0, 0.1], max_iters=60, jit=False)
    names = [s.name for s in rec.spans]
    assert names.count("mle.fit") == 1
    assert names.count("mle.iter") == res.n_iters > 1
    assert names.count("mle.eval") == res.n_evals
    assert names.count("mle.theta") == res.n_evals
    hist = rec.snapshot()["histograms"]
    assert hist["mle.eval"]["count"] == res.n_evals
    assert hist["mle.iter"]["count"] == res.n_iters
    assert not any(k.startswith("mle.eval_seconds") for k in hist)
    # the initial simplex (d + 1 points) is evaluated before the first
    # iteration, inside mle.fit; every other evaluation inside an mle.iter
    depths = [s.depth for s in rec.spans if s.name == "mle.eval"]
    assert depths.count(1) == 3 and depths.count(2) == res.n_evals - 3


def test_mle_batched_calls_are_mle_eval_batch_spans():
    from repro.core.mle import fit_mle, fit_mle_grid

    batched = jax.vmap(_peaked)
    with obs.recording() as rec:
        fit_mle_grid(batched, [(0.5, 3.0), (0.05, 0.8)], num=4, refine=3)
    assert [s.name for s in rec.spans].count("mle.eval_batch") == 3
    with obs.recording() as rec:
        res = fit_mle(None, [1.0, 0.1], max_iters=30,
                      batched_loglik_fn=batched)
    names = [s.name for s in rec.spans]
    assert names.count("mle.iter") == res.n_iters
    assert names.count("mle.eval") == 0
    # one batched call per iteration that did not converge, plus the
    # initial simplex and one per shrink
    assert res.n_iters <= names.count("mle.eval_batch") <= 2 * res.n_iters
    assert not any(k.startswith("mle.eval_batch_seconds")
                   for k in rec.snapshot()["histograms"])


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------

def test_recorder_thread_safety_raw_threads():
    rec = obs.Recorder()
    n_threads, n_iter = 8, 200

    def work():
        for _ in range(n_iter):
            rec.inc("c")
            rec.observe("h", 1e-4)
            with rec.span("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = rec.snapshot()
    assert snap["counters"]["c"] == n_threads * n_iter
    assert snap["histograms"]["h"]["count"] == n_threads * n_iter
    assert len(snap["spans"]) == n_threads * n_iter
    # per-thread depth stacks never bled across threads
    assert all(s.depth == 0 for s in snap["spans"])


def test_recorder_under_threaded_executor():
    """The scheduler's worker pool writes task histograms concurrently."""
    a = spd_matrix(5, 128, cond=100.0)
    with obs.recording() as rec:
        l, report = scheduled_tile_cholesky(
            a, 32, POLICY, SchedConfig(backend="real", workers=4))
    snap = rec.snapshot()
    n_observed = sum(h["count"] for name, h in snap["histograms"].items()
                     if name.startswith("sched.task."))
    assert n_observed == report.n_tasks
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("sched.tasks.")) == report.n_tasks
    assert "sched.t0" in snap["gauges"]
    assert any(s.name == "sched.execute" for s in snap["spans"])
    # and the factorization itself is still right
    np.testing.assert_allclose(np.asarray(l), np.asarray(
        tile_cholesky(a, 32, POLICY)), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _populated_recorder() -> obs.Recorder:
    rec = obs.Recorder()
    with rec.span("alpha", n=1):
        time.sleep(0.001)
        with rec.span("beta"):
            pass
    try:
        with rec.span("beta"):
            raise ValueError("x")
    except ValueError:
        pass
    rec.inc("count.a", 3)
    rec.gauge("g", 2.5)
    rec.observe("lat", 0.02)
    return rec


def test_jsonl_round_trip(tmp_path):
    rec = _populated_recorder()
    path = tmp_path / "metrics.jsonl"
    n = obs.write_jsonl(rec, path)
    evs = obs.load_jsonl(path)
    assert len(evs) == n
    # aggregates rebuilt from the file match those from the live recorder
    assert obs.summary_from_events(evs) == obs.summary_rows(rec)
    by_type = {}
    for ev in evs:
        by_type.setdefault(ev["type"], []).append(ev)
    assert len(by_type["span"]) == 3
    assert {e["name"] for e in by_type["counter"]} == {"count.a"}
    hist_names = {e["name"] for e in by_type["histogram"]}
    assert {"alpha", "beta", "lat"} <= hist_names
    # every line is valid standalone JSON (append-friendly contract)
    for line in path.read_text().splitlines():
        json.loads(line)


def test_summary_rows_aggregate():
    rec = _populated_recorder()
    rows = {r["name"]: r for r in obs.summary_rows(rec)}
    assert rows["beta"]["count"] == 2
    assert rows["beta"]["errors"] == 1
    assert rows["alpha"]["count"] == 1
    assert rows["alpha"]["total"] >= 0.001


def test_summary_table_renders():
    table = obs.summary_table(_populated_recorder())
    assert "alpha" in table and "count.a" in table and "lat" in table
    assert obs.summary_table(obs.Recorder()) == "(recorder is empty)"


def test_prometheus_text():
    rec = obs.Recorder()
    rec.inc("tasks.done", 5)
    rec.gauge("t0", 1.5)
    h = obs.Histogram(edges=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    rec.histograms["lat"] = h
    text = obs.prometheus_text(rec)
    assert "# TYPE repro_tasks_done counter" in text
    assert "repro_tasks_done 5" in text
    assert "repro_t0 1.5" in text
    # cumulative le buckets + +Inf + sum/count
    assert 'repro_lat_bucket{le="0.1"} 1' in text
    assert 'repro_lat_bucket{le="1"} 2' in text
    assert 'repro_lat_bucket{le="+Inf"} 3' in text
    assert "repro_lat_count 3" in text


# ---------------------------------------------------------------------------
# merged Chrome trace
# ---------------------------------------------------------------------------

def test_merged_trace_validates_with_both_streams(tmp_path):
    a = spd_matrix(7, 128, cond=100.0)
    with obs.recording() as rec:
        with obs.span("host.outer"):
            with obs.span("host.inner"):
                scheduled_tile_cholesky(
                    a, 32, POLICY, SchedConfig(backend="real", workers=2))
    # grab the report again without telemetry for the trace
    with obs.recording():
        _, report = scheduled_tile_cholesky(
            a, 32, POLICY, SchedConfig(backend="real", workers=2))
    path = tmp_path / "merged.json"
    trace = obs.write_merged_trace(report, rec, path)
    validate_trace(trace)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    pids = {e["pid"] for e in xs}
    assert pids == {0, 1}                      # scheduler tasks + host spans
    assert trace["otherData"]["host_spans"] == len(rec.spans)
    # nested host spans land on distinct depth tracks
    host = [e for e in xs if e["pid"] == 1]
    outer = next(e for e in host if e["name"] == "host.outer")
    inner = next(e for e in host if e["name"] == "host.inner")
    assert outer["tid"] != inner["tid"]
    validate_trace(json.loads(path.read_text()))


def test_merged_trace_without_spans_is_plain_sched_trace():
    rep = simulate(build_graph("tile", 4, POLICY),
                   SchedConfig(backend="sim", workers=2))
    trace = obs.merged_chrome_trace(rep, obs.Recorder())
    assert "host_spans" not in trace["otherData"]
    validate_trace(trace)


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

# every execution pair the engines emit (lo2 is storage-only; CONVERTs
# carry it -- see obs/calibrate.py)
EXPECTED_KEYS = {"POTRF/hi", "TRSM/hi", "TRSM/lo", "SYRK/hi", "GEMM/hi",
                 "GEMM/lo", "CONVERT"}


def test_measure_kernel_times_covers_every_pair():
    costs, meta = measure_kernel_times(nb=16, p=4, reps=1)
    assert set(costs) == EXPECTED_KEYS
    assert all(v > 0 for v in costs.values())
    assert meta["units"] == "microseconds"
    graph = build_graph("tile", 4, POLICY)
    assert {cost_key(t) for t in graph.tasks} == EXPECTED_KEYS


def test_write_calibration_round_trip(tmp_path):
    costs = {k: float(i + 1) for i, k in enumerate(sorted(EXPECTED_KEYS))}
    path = write_calibration(costs, {"units": "microseconds"},
                             tmp_path / "cal.json")
    loaded = load_calibration(path)
    assert loaded == {k: round(v, 3) for k, v in costs.items()}


class _FakeTask:
    def __init__(self, kind, tier):
        self.kind, self.tier = kind, tier


def test_task_virtual_cost_calibrated_table():
    table = {"GEMM/lo": 123.0, "CONVERT": 7.0}
    assert task_virtual_cost(_FakeTask("GEMM", "lo"), calibrated=True,
                             table=table) == 123.0
    assert task_virtual_cost(_FakeTask("CONVERT", "lo"), calibrated=True,
                             table=table) == 7.0
    # keys the table lacks fall back to the analytic weight
    analytic = task_virtual_cost(_FakeTask("POTRF", "hi"))
    assert task_virtual_cost(_FakeTask("POTRF", "hi"), calibrated=True,
                             table=table) == analytic


def test_task_virtual_cost_requires_some_table(monkeypatch, tmp_path):
    from repro.launch import costmodel
    monkeypatch.setattr(costmodel, "CALIBRATION_PATH",
                        tmp_path / "missing.json")
    set_calibration(None)        # drop any cached table
    try:
        with pytest.raises(FileNotFoundError):
            task_virtual_cost(_FakeTask("GEMM", "lo"), calibrated=True)
    finally:
        set_calibration(None)    # re-read the real file next time


def test_simulator_responds_to_measured_weights():
    """The acceptance gate: sim makespans/ordering follow the measured
    table, not the analytic weights, when `calibrated=True`."""
    graph = build_graph("tile", 8, POLICY)
    cfg = SchedConfig(backend="sim", workers=4, priority="critical_path")
    base = simulate(graph, cfg)
    # invert the analytic world: CONVERTs and lo math dominate
    table = {"POTRF/hi": 1.0, "TRSM/hi": 1.0, "SYRK/hi": 1.0, "GEMM/hi": 1.0,
             "TRSM/lo": 50.0, "GEMM/lo": 80.0, "CONVERT": 200.0}
    set_calibration(table)
    try:
        cal = simulate(graph, SchedConfig(backend="sim", workers=4,
                                          priority="critical_path",
                                          calibrated=True))
    finally:
        set_calibration(None)
    assert cal.makespan != base.makespan
    # per-task durations in the calibrated schedule match the table
    ev = next(e for e in cal.events if e.kind == "CONVERT")
    assert ev.end - ev.start == pytest.approx(200.0)
    order_base = [e.index for e in sorted(base.events, key=lambda e: (e.start, e.index))]
    order_cal = [e.index for e in sorted(cal.events, key=lambda e: (e.start, e.index))]
    assert order_base != order_cal       # priorities reordered dispatch


def test_sched_config_validates_calibrated_flag():
    with pytest.raises(ValueError):
        SchedConfig(backend="sim", calibrated="yes")


# ---------------------------------------------------------------------------
# disabled-mode overhead guard
# ---------------------------------------------------------------------------

def test_disabled_overhead_under_five_percent():
    """Telemetry off must cost < 5% on a p=8 tile factorization.

    Measured conservatively: per-call cost of a disabled maybe_span x a
    generous estimate of call sites per factorization, against the
    measured factorization wall time.
    """
    assert not obs.enabled()
    a = spd_matrix(9, 256, cond=100.0)
    arr = jnp.asarray(a)

    tile_cholesky(arr, 32, POLICY).block_until_ready()       # warm up
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        tile_cholesky(arr, 32, POLICY).block_until_ready()
    chol_s = (time.perf_counter() - t0) / reps

    n_calls = 20_000                 # >> the handful of real guard checks
    t0 = time.perf_counter()
    for _ in range(n_calls):
        with obs.maybe_span("x", arr):
            pass
    per_call = (time.perf_counter() - t0) / n_calls

    # a p=8 factorization crosses O(p^3) ~ 120 tile ops; budget 10x that
    overhead = per_call * 1200
    assert overhead < 0.05 * chol_s, (
        f"disabled-mode telemetry too expensive: {per_call * 1e9:.0f} ns/call"
        f" x 1200 sites = {overhead * 1e3:.3f} ms vs factorization"
        f" {chol_s * 1e3:.1f} ms")


# ---------------------------------------------------------------------------
# high-contention stress (PR 10): the single-lock recorder loses nothing
# ---------------------------------------------------------------------------

def test_recorder_contention_no_lost_updates():
    """N raw threads hammering ONE counter + ONE histogram: every
    increment and observation lands; bucket sums match the total count."""
    rec = obs.Recorder()
    n_threads, per_thread = 8, 500
    barrier = threading.Barrier(n_threads)

    def hammer(t):
        barrier.wait()          # maximize overlap
        for i in range(per_thread):
            rec.inc("hits")
            rec.observe("lat", (t * per_thread + i) % 7 * 1e-4)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    total = n_threads * per_thread
    snap = rec.snapshot()
    assert snap["counters"]["hits"] == total
    h = rec.histograms["lat"]
    assert h.count == total
    assert sum(h.counts) == total                   # bucket partition
    assert h.bucket_rows()[-1] == (float("inf"), total)  # cumulative top
    assert h.min >= 0.0 and h.max <= 6.1e-4


def test_recorder_contention_spans_and_mixed_ops():
    """Concurrent spans + counters + gauges: span list complete, nesting
    depths consistent, histogram auto-created by span finish is exact."""
    rec = obs.Recorder()
    n_threads, per_thread = 6, 120
    barrier = threading.Barrier(n_threads)

    def hammer(t):
        barrier.wait()
        for i in range(per_thread):
            with rec.span("outer", t=t):
                with rec.span("inner"):
                    rec.inc("ops")
            rec.gauge(f"g{t}", float(i))

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    total = n_threads * per_thread
    snap = rec.snapshot()
    assert snap["counters"]["ops"] == total
    assert len(snap["spans"]) == 2 * total
    by_name = {}
    for s in snap["spans"]:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["outer"]) == total
    assert len(by_name["inner"]) == total
    assert all(s.depth == 0 for s in by_name["outer"])
    assert all(s.depth == 1 for s in by_name["inner"])
    assert rec.histograms["outer"].count == total
    assert rec.histograms["inner"].count == total
    assert snap["gauges"] == {f"g{t}": float(per_thread - 1)
                              for t in range(n_threads)}
