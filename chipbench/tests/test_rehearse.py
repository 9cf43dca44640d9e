"""Every cell's traffic at its rehearsal size on the CPU, and the layout
a later PR extends by adding files and entries alone."""

import json
import subprocess
import sys

import pytest

from chipbench import drivers, run, spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_rehearse_cell(name):
    cell = spec.cell(name)
    res = run.run_cell(cell, seed=2 ** 31 + 77, seconds=4.0, traced=False,
                       rehearse=True, counters=run.Counters(),
                       log=lambda *a, **k: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(res)[-1] == "checks"
    # the compiled program's own bytes, where the runtime counts none
    assert res["device"]["memory_peak_bytes"] > 0


def test_rehearse_command_prints_no_result(capsys):
    assert run.main(["--rehearse", "--workload", CELLS[0]]) == 0
    out = capsys.readouterr().out
    assert "rehearsed" in out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_every_file_is_found_by_name():
    used_cfg = {w["config"] for w in BENCH["workloads"]}
    used_traffic = {w["traffic"] for w in BENCH["workloads"]}
    files = {c["file"] for c in BENCH["configs"]}
    assert {p.relative_to(spec.ROOT).as_posix()
            for p in (spec.HERE / "configs").glob("*.json")} == files
    assert {c["name"] for c in BENCH["configs"]} == used_cfg
    assert {p.stem for p in (spec.HERE / "traffic").glob("*.json")} == used_traffic
    kinds = {p.stem for p in (spec.HERE / "kinds").glob("*.py")} - {"__init__"}
    for stem in kinds:
        mod = drivers.kind(stem)
        assert mod.Driver and mod.CHIPS
    readers = {p.stem for p in (spec.HERE / "metrics").glob("*.py")}
    assert readers == {m["name"] for m in BENCH["per_layer"]}
    for name in CELLS:
        cell = spec.cell(name)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)
        assert cell.traffic["kind"] in kinds
        assert cell.chips in drivers.kind(cell.traffic["kind"]).CHIPS
        assert cell.config["reduced"] == next(
            c for c in BENCH["configs"] if c["name"] == cell.config["name"]
        )["reduced"]


def test_a_kind_refuses_a_chip_count_it_does_not_place_work_on():
    cell = spec.cell(CELLS[0])
    ctx = drivers.Ctx.make(cell.config, cell.traffic, 1, rehearse=True)
    with pytest.raises(ValueError, match="chip"):
        drivers.driver(ctx, 4)


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0
    assert "{" not in capsys.readouterr().out


def test_benchmark_alone_fails_without_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's files has
    no system to measure: non-zero exit, no result line."""
    import shutil
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(spec.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert json.loads((tmp_path / "BENCHMARK.json").read_text()) == BENCH
