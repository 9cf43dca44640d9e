"""Device time by named scope, read from the trace's `tf_op` stats; the
metrics that read it; and the programs the scopes are in."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import peaks, scopes, spec, trace
from chipbench.kinds.evals import eval_fn
from chipbench.kinds.predict import krige_fn

DATA = Path(__file__).parent / "data"
UNSCOPED = DATA / "evals_tiny.xplane.pb"        # a program without the scopes
SCOPED = DATA / "evals_scoped_tiny.xplane.pb"   # the same traffic, with them
PINNED = DATA / "evals_tiny.pinned.json"
NEW_METRICS = {"synth32k.evals": ["cov_build_ms", "factor_ms", "solve_ms",
                                  "factor_panel_ms", "factor_update_hi_ms",
                                  "factor_update_lo_ms"],
               "synth8k.predict": ["krige_cov_build_ms", "krige_factor_ms",
                                   "krige_solve_ms"]}
STEP = "geostat_loglik_step"


def _rctx(path):
    return SimpleNamespace(trace=trace.reduce(path), counters={},
                           ctx=SimpleNamespace(n=256),
                           peak=peaks.peak("TPU v5 lite"))


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name,path", [
    ("jit(f)/jit(main)/a/b/dot_general", "a/b"),
    ("jit(f)/a/vmap(jit(g))/b/exp", "a/b"),
    ("jit(f)/a/b/jit(cholesky)", "a/b"),
    ("jit(f)/a/b/jit(cholesky)/cholesky:", "a/b"),
    ("jit(f)/a/iab,icb->iac/dot_general", "a/iab,icb->iac"),
    ("jit(f)/a/b/mul;jit(f)/c/add", "a/b"),
    ("reduce_sum", scopes.NO_SCOPE),
    ("", scopes.NO_SCOPE),
])
def test_scope_path(op_name, path):
    assert scopes.scope_path(op_name) == path


# ---------------------------------------------------------------------------
# hand-made programs and traces
# ---------------------------------------------------------------------------

def _module(name, computations):
    """An HloModuleProto: computations [(id, name, root id, [(id, name,
    opcode, op_name, operand ids, called computation ids)])]."""
    module = scopes.schema()["HloModuleProto"](name=name)
    for cid, cname, root, instructions in computations:
        comp = module.computations.add(id=cid, name=cname, root_id=root)
        for iid, iname, opcode, op_name, operands, called in instructions:
            ins = comp.instructions.add(id=iid, name=iname, opcode=opcode,
                                        operand_ids=operands,
                                        called_computation_ids=called)
            ins.metadata.op_name = op_name
    return module


J = "jit(cb_eval)/"
MODULE = _module("jit_cb_eval", [
    (1, "fused_computation", 11, [
        (10, "param_0", "parameter", "", [], []),
        (11, "mul.1", "multiply", J + "s/factor/update_lo/jit(matmul)/mul",
         [10, 10], [])]),
    (2, "body", 23, [
        (20, "p", "parameter", "", [], []),
        (21, "gte.1", "get-tuple-element", "", [20], []),
        (22, "dus.1", "dynamic-update-slice", "", [21, 21], []),
        (23, "t.1", "tuple", "", [22], [])]),
    (4, "cond", 40, [(40, "c.1", "parameter", "", [], [])]),
    (3, "main.9", 37, [
        (30, "locs.1", "parameter", "locs", [], []),
        (31, "cholesky.3", "custom-call",
         J + "s/factor/potrf/jit(cholesky)/cholesky", [30], []),
        (32, "copy.7", "copy", "", [31], []),
        (33, "fusion.2", "fusion", J + "s/factor/gather/convert_element_type",
         [32], [1]),
        (34, "tuple.4", "tuple", "", [33], []),
        (35, "while.5", "while",
         J + "s/solve/jit(_solve_triangular)/triangular_solve", [34], [4, 2]),
        (36, "add.6", "add", J + "s/cov_build/vmap(vmap())/sub;" + J + "x/y",
         [33, 33], []),
        (37, "out.8", "reduce", J + "reduce_sum", [36], [])]),
])


def test_scope_map_reads_op_name_metadata():
    got = scopes.scope_map(MODULE)
    assert got["cholesky.3"] == "s/factor/potrf"
    # a fusion takes its fused root's scope
    assert got["fusion.2"] == "s/factor/update_lo"
    # a copy the compiler put in: the common scope of what it reads and feeds
    assert got["copy.7"] == "s/factor"
    # a loop body's unscoped instructions: the loop's scope
    assert got["dus.1"] == "s/solve"
    # merged names: the first; transforms dropped
    assert got["add.6"] == "s/cov_build"
    assert got["out.8"] == scopes.NO_SCOPE
    assert got["locs.1"] == scopes.NO_SCOPE


def _space(ops, spans, modules):
    """An XSpace with one host line of `spans` [(name, start_ns, dur_ns)],
    one device `XLA Ops` line of `ops` [(program id, instruction,
    start_ns, dur_ns)], and the HLO of `modules` {program id: module}."""
    S = scopes.schema()
    space = S["XSpace"]()
    meta = space.planes.add(name=scopes.METADATA_PLANE)
    meta.stat_metadata.add(key=1).value.name = scopes.HLO_STAT
    for pid, module in modules.items():
        md = meta.event_metadata.add(key=pid).value
        md.name = f"{module.name}({pid})"
        md.stats.add(metadata_id=1, bytes_value=S["HloProto"](
            hlo_module=module).SerializeToString())
    host = space.planes.add(name="/host:CPU")
    ids = {}
    for n, _, _ in spans:
        if n not in ids:
            ids[n] = len(ids) + 1
            host.event_metadata.add(key=ids[n]).value.name = n
    line = host.lines.add(name="python3", timestamp_ns=1000)
    for n, s, d in spans:
        line.events.add(metadata_id=ids[n], offset_ps=(s - 1000) * 1000,
                        duration_ps=d * 1000)
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=1).value.name = "flops"
    dev.stat_metadata.add(key=2).value.name = "program_id"
    ids = {}
    for pid, n, _, _ in ops:
        if (pid, n) in ids:
            continue
        ids[pid, n] = len(ids) + 1
        md = dev.event_metadata.add(key=ids[pid, n]).value
        md.name = f"%{n} = f32[8] op()"
        md.stats.add(metadata_id=1, int64_value=7)
        md.stats.add(metadata_id=2, uint64_value=pid)
    line = dev.lines.add(name=trace.OPS_LINE, timestamp_ns=0)
    for pid, n, s, d in ops:
        line.events.add(metadata_id=ids[pid, n], offset_ps=s * 1000,
                        duration_ps=d * 1000)
    dev.lines.add(name=trace.MODULES_LINE, timestamp_ns=0)
    return space


PID = (1 << 63) + 5        # program ids are unsigned 64-bit
OPS = [(PID, "cholesky.3", 50, 100),     # 100..150 inside the window
       (PID, "copy.7", 150, 50),
       (PID, "fusion.2", 200, 100),
       (PID, "add.6", 300, 20),
       (PID, "mystery.1", 320, 30),      # not in the program
       (PID, "while.5", 360, 60),        # holds:
       (PID, "dus.1", 370, 20),          # its body
       (PID, "add.6", 400, 10),
       (9, "exp.1", 700, 40),            # a program whose HLO is absent
       (PID, "fusion.2", 1050, 200)]     # 1050..1100 inside
WINDOW = [("cb.window", 100, 1000), ("cb.eval", 100, 400)]


def _signed(pid):
    return pid - (1 << 64) if pid >= 1 << 63 else pid


def test_scopes_sum_self_time_by_program_and_path_within_the_window():
    got = scopes.reduce_space(_space(OPS, WINDOW, {_signed(PID): MODULE}))
    assert got.window_s == pytest.approx(1000e-9)
    ns = {k: {p: round(s * 1e9) for p, s in d.items()}
          for k, d in got.seconds.items()}
    # the loop counts its own 30 ns, its body ops their 30 ns
    assert ns == {"cb_eval": {"s/factor/potrf": 50, "s/factor": 50,
                              "s/factor/update_lo": 150,
                              "s/cov_build": 20 + 10, "s/solve": 30 + 20,
                              scopes.NO_SCOPE: 30},
                  None: {scopes.NO_SCOPE: 40}}
    assert got.total("cb_eval", "s/factor") == pytest.approx(250e-9)
    assert got.total("cb_eval", "s/factor/potrf",
                     "s/cov_build") == pytest.approx(80e-9)
    assert got.total("cb_eval", "s/update") is None
    assert got.total("cb_krige", "s/factor") is None
    assert got.share() == pytest.approx(70 / 400)


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError, match="cb.window"):
        scopes.reduce_space(_space(OPS, [("cb.eval", 0, 9)], {}))


def test_scope_ms_is_per_execution_of_the_program(tmp_path, monkeypatch):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space(OPS, WINDOW, {_signed(PID): MODULE})
                     .SerializeToString())
    monkeypatch.setattr(scopes, "traced_file", lambda: path)
    red = trace.Reduction(window_s=1e-6, busy_s=4e-7, chips=1,
                          modules={"jit_cb_eval": [4e-7, 2]})
    rctx = SimpleNamespace(trace=red)
    assert scopes.scope_ms(rctx, "cb_eval", "s/factor") == \
        pytest.approx(250e-6 / 2)
    assert scopes.scope_ms(rctx, "cb_eval", "s/krige") is None
    assert scopes.scope_ms(rctx, "cb_krige", "s/factor") is None


# ---------------------------------------------------------------------------
# traces from the chip
# ---------------------------------------------------------------------------

def test_existing_fields_read_as_before_on_the_chip_trace():
    """`busy_s`, `window_s`, `ops`, `gaps` and `modules` of the recorded
    trace, pinned in evals_tiny.pinned.json."""
    want = json.loads(PINNED.read_text())
    red = trace.reduce(UNSCOPED)
    assert red.busy_s == want["busy_s"]
    assert red.window_s == want["window_s"]
    assert red.chips == want["chips"]
    assert red.modules == want["modules"]
    assert red.ops == want["ops"]
    assert len(red.gaps) == want["gaps_count"]
    assert sum(s for _, s in red.gaps) == pytest.approx(want["gaps_total_s"],
                                                        rel=1e-12)
    assert [list(g) for g in red.gaps[:10]] == want["gaps_top"]


@pytest.mark.parametrize("name", ["eval_mfu_pct", "device_idle_pct.eval",
                                  "device_idle_pct.fit",
                                  "device_idle_pct.predict", "krige_device_ms",
                                  "evals_per_fit"])
def test_existing_metrics_read_as_before_on_the_chip_trace(name):
    want = json.loads(PINNED.read_text())["metrics"][name]
    assert spec.reader(name)(_rctx(UNSCOPED)) == want


@pytest.mark.parametrize("name", [m for ms in NEW_METRICS.values()
                                  for m in ms])
def test_new_metrics_read_nothing_on_a_program_without_scopes(
        monkeypatch, name):
    monkeypatch.setattr(scopes, "traced_file", lambda: UNSCOPED)
    assert spec.reader(name)(_rctx(UNSCOPED)) is None


def test_new_metrics_are_declared_for_their_cells():
    per_layer = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for cell, names in NEW_METRICS.items():
        for name in names:
            assert per_layer[name]["workloads"] == [cell]
            assert per_layer[name]["source"] == "device_trace"
            assert (spec.HERE / "metrics" / f"{name}.py").is_file()


def test_scoped_chip_trace_of_the_evals_traffic(monkeypatch):
    """Recorded on a v5e by tests/record_scoped_trace.py: 95% or more of
    the device time falls under a named scope, the three parts of the
    step hold 95% of the busy time per evaluation, and the factor's
    phases no more than the factor."""
    monkeypatch.setattr(scopes, "traced_file", lambda: SCOPED)
    rctx = _rctx(SCOPED)
    got = scopes.reduce(SCOPED)
    busy = sum(s for d in got.seconds.values() for s in d.values())
    assert busy == pytest.approx(rctx.trace.busy_s, rel=1e-2)
    assert got.share() <= 0.05
    read = {m: spec.reader(m)(rctx) for m in NEW_METRICS["synth32k.evals"]}
    assert all(v and v > 0 for v in read.values())
    per_eval_ms = rctx.trace.busy_s / rctx.trace.executions("cb_eval") * 1e3
    assert read["cov_build_ms"] + read["factor_ms"] + read["solve_ms"] \
        >= 0.95 * per_eval_ms
    assert read["factor_panel_ms"] + read["factor_update_hi_ms"] \
        + read["factor_update_lo_ms"] <= read["factor_ms"]
    # the kriging program is not in this trace
    assert all(spec.reader(m)(rctx) is None
               for m in NEW_METRICS["synth8k.predict"])


# ---------------------------------------------------------------------------
# the cells' programs
# ---------------------------------------------------------------------------

def test_scope_map_of_the_compiled_step_on_the_cpu():
    """The HLO proto of the compiled step, which the profiler puts in the
    trace: the Cholesky under `factor/potrf`, the triangular solves under
    `factor/trsm_*` or `solve`."""
    import jax

    from chipbench import drivers

    cell = spec.cell("synth32k.evals")
    ctx = drivers.Ctx.make(cell.config, cell.traffic, 1, rehearse=True)
    args = (jax.numpy.zeros((ctx.n, 2)), jax.numpy.zeros(ctx.n),
            jax.numpy.ones(2))
    exe = jax.jit(eval_fn(ctx)).lower(*args).compile()
    module = scopes.schema()["HloModuleProto"]()
    module.ParseFromString(exe.runtime_executable().hlo_modules()[0]
                           .as_serialized_hlo_module_proto())
    assert module.name == "jit_cb_eval"
    got = scopes.scope_map(module)
    chol = [v for k, v in got.items() if k.startswith("cholesky")]
    solves = [v for k, v in got.items() if k.startswith("triangular_solve")]
    assert chol and solves
    factor = f"{STEP}/factor/"
    assert all(p == factor + "potrf" for p in chol)
    assert any(p.startswith(factor) for p in solves)
    assert all(p in (factor + "trsm_hi", factor + "trsm_lo", f"{STEP}/solve")
               for p in solves)


@pytest.mark.parametrize("name", ["synth32k.evals", "synth8k.predict"])
def test_cell_programs_lower_the_same_without_scopes(monkeypatch, name):
    """`cb_eval` and `cb_krige` at the rehearsal size: the named scopes
    are metadata, so the program lowered with `jax.named_scope` made a
    null context is the same once source locations are left out."""
    import contextlib

    import jax

    from chipbench import drivers

    cell = spec.cell(name)
    ctx = drivers.Ctx.make(cell.config, cell.traffic, 7, rehearse=True)
    drv = drivers.driver(ctx)
    drv.setup()
    exe_args = drv.thetas[0] if name == "synth32k.evals" else drv.inputs[0]
    fn = (eval_fn if name == "synth32k.evals" else krige_fn)(ctx)
    scoped = jax.jit(fn).lower(*exe_args)
    assert "/factor/potrf/" in scoped.as_text(debug_info=True)
    jax.clear_caches()               # else the traced program comes back
    monkeypatch.setattr(jax, "named_scope",
                        lambda n: contextlib.nullcontext())
    plain = jax.jit(fn).lower(*exe_args)
    assert "/factor/potrf/" not in plain.as_text(debug_info=True)
    assert scoped.as_text() == plain.as_text()
    drv.free()
