"""Good/bad fixtures for the precision-flow linter (repro.analysis.lint).

Each rule gets a minimal snippet pair: the bad one must produce exactly the
expected finding, the good one must be clean.  The suite also pins the two
meta-properties the CI gate relies on: the repo at HEAD is lint-clean modulo
the committed baseline, and a seeded violation in a core engine is caught.
"""

import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis.baseline import (
    load_baseline,
    split_baselined,
    update_baseline,
)
from repro.analysis.cli import SRC_ROOT, main, run_lint
from repro.analysis.lint import (
    Finding,
    check_kernel_package,
    lint_source,
    lint_tree,
    pragma_lines,
)

CORE = "repro/core/fixture.py"          # strict package
RUNTIME = "repro/runtime/fixture.py"    # non-strict package
KERNEL = "repro/kernels/fixture/kernel.py"


def lint(src: str, relpath: str = CORE):
    return lint_source(textwrap.dedent(src), relpath)


def rules(findings):
    return [f.rule for f in findings]


# ---- no-implicit-downcast -------------------------------------------------

def test_literal_astype_flagged_in_strict_package():
    fs = lint("x = a.astype(jnp.float32)\n")
    assert rules(fs) == ["no-implicit-downcast"]
    assert "policy-scoped" in fs[0].message


def test_string_literal_astype_flagged_in_strict_package():
    assert rules(lint('x = a.astype("float64")\n')) == ["no-implicit-downcast"]


def test_policy_field_astype_clean():
    assert lint("x = a.astype(policy.hi)\n") == []


def test_dtype_variable_astype_clean():
    assert lint("x = a.astype(dtype)\ny = b.astype(a.dtype)\n") == []


def test_widening_literal_legal_outside_strict_packages():
    # fp32 upcast is the documented MXU-accumulate idiom outside core/
    assert lint("x = a.astype(jnp.float32)\n", RUNTIME) == []


def test_narrowing_literal_flagged_everywhere():
    fs = lint("x = a.astype(jnp.bfloat16)\n", RUNTIME)
    assert rules(fs) == ["no-implicit-downcast"]
    assert "narrowing" in fs[0].message


@pytest.mark.parametrize("dt", ["float16", "float8_e4m3fn", "int8"])
def test_all_narrow_dtypes_covered(dt):
    assert rules(lint(f"x = a.astype(jnp.{dt})\n", RUNTIME)) \
        == ["no-implicit-downcast"]


# ---- pragma suppression ---------------------------------------------------

def test_inline_pragma_suppresses():
    src = ("x = a.astype(jnp.bfloat16)"
           "  # repro: disable=no-implicit-downcast -- wire format\n")
    assert lint(src, RUNTIME) == []


def test_pragma_for_other_rule_does_not_suppress():
    src = "x = a.astype(jnp.bfloat16)  # repro: disable=x64-guard\n"
    assert rules(lint(src, RUNTIME)) == ["no-implicit-downcast"]


def test_multi_rule_pragma():
    src = ("x = a.astype(jnp.bfloat16)"
           "  # repro: disable=x64-guard,no-implicit-downcast\n")
    assert lint(src, RUNTIME) == []


def test_pragma_on_any_line_of_multiline_statement():
    src = (
        "x = a.astype(\n"
        "    jnp.bfloat16\n"
        ")  # repro: disable=no-implicit-downcast -- spans three lines\n")
    assert lint(src, RUNTIME) == []


def test_pragma_parse():
    got = pragma_lines("a = 1  # repro: disable=accum-dtype, x64-guard\n")
    assert got == {1: frozenset({"accum-dtype", "x64-guard"})}


# ---- accum-dtype ----------------------------------------------------------

def test_lo_cast_operand_without_accumulator_flagged():
    src = """
    def f(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16), b)
    """
    fs = lint(src, RUNTIME)
    assert "accum-dtype" in rules(fs)
    assert "preferred_element_type" in [f for f in fs
                                        if f.rule == "accum-dtype"][0].message


def test_policy_lo_cast_without_accumulator_flagged():
    src = """
    def f(a, b, policy):
        return jnp.matmul(a.astype(policy.lo), b)
    """
    assert "accum-dtype" in rules(lint(src, RUNTIME))


def test_explicit_policy_accumulator_clean():
    src = """
    def f(a, b, policy):
        al = a.astype(policy.lo)
        return jnp.matmul(al, b, preferred_element_type=policy.accum_dtype)
    """
    assert lint(src, RUNTIME) == []


def test_narrow_literal_accumulator_flagged():
    src = """
    def f(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.bfloat16)
    """
    fs = lint(src, RUNTIME)
    assert rules(fs) == ["accum-dtype"]
    assert "narrow literal accumulator" in fs[0].message


def test_taint_through_locals():
    # dtype var bound to a lo tier, array var bound to the lo-cast value:
    # the matmul two hops away must still be flagged
    src = """
    def f(a, b, policy):
        wire = policy.lo
        aq = a.astype(wire)
        return jnp.matmul(aq, b)
    """
    assert "accum-dtype" in rules(lint(src, RUNTIME))


def test_hi_matmul_clean():
    src = """
    def f(a, b):
        return jnp.matmul(a, b)
    """
    assert lint(src, RUNTIME) == []


# ---- x64-guard ------------------------------------------------------------

def test_float64_outside_x64_module_flagged():
    fs = lint("x = jnp.float64\n", RUNTIME)
    assert rules(fs) == ["x64-guard"]
    assert "truncates" in fs[0].message


def test_float64_legal_when_module_enables_x64():
    src = """
    with jax.enable_x64(True):
        x = jnp.float64
    """
    assert lint(src, RUNTIME) == []


def test_float64_legal_with_module_marker():
    src = """
    # repro: x64-module -- CPU statistical validation path
    x = jnp.float64
    """
    assert lint(src, RUNTIME) == []


def test_np_float64_not_flagged():
    # host-side numpy fp64 is real fp64; only jnp.float64 silently truncates
    assert lint("x = np.float64\n", RUNTIME) == []


# ---- pallas-blockspec-contract: pallas_call structure ---------------------

GOOD_PALLAS = """
def op(x):
    return pl.pallas_call(
        kern,
        grid=(2, 2),
        in_specs=[pl.BlockSpec((128, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((128, 128), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((256, 256), x.dtype),
    )(x)
"""


def test_good_pallas_call_clean():
    assert lint(GOOD_PALLAS, KERNEL) == []


def test_index_map_arity_vs_grid_rank():
    bad = GOOD_PALLAS.replace("lambda i, j: (i, j))]", "lambda i: (i, 0))]")
    fs = lint(bad, KERNEL)
    assert rules(fs) == ["pallas-blockspec-contract"]
    assert "grid has rank 2" in fs[0].message


def test_block_shape_rank_vs_index_rank():
    bad = GOOD_PALLAS.replace("grid=(2, 2)", "grid=(2,)") \
                     .replace("lambda i, j: (i, j)", "lambda i: i")
    fs = lint(bad, KERNEL)
    assert fs and all(f.rule == "pallas-blockspec-contract" for f in fs)
    assert any("rank 2 but its" in f.message for f in fs)


def test_out_shape_out_specs_count_mismatch():
    src = """
    def op(x):
        return pl.pallas_call(
            kern,
            grid=(2,),
            out_specs=[pl.BlockSpec((128,), lambda i: i)],
            out_shape=(jax.ShapeDtypeStruct((256,), x.dtype),
                       jax.ShapeDtypeStruct((256,), x.dtype)),
        )(x)
    """
    fs = lint(src, KERNEL)
    assert any("declares 2 outputs but out_specs declares 1" in f.message
               for f in fs)


def test_pallas_rules_only_run_in_kernels_package():
    bad = GOOD_PALLAS.replace("lambda i, j: (i, j))]", "lambda i: (i, 0))]")
    assert lint(bad, RUNTIME) == []


# ---- pallas-blockspec-contract: ops.py <-> ref.py conformance -------------

def _kernel_pkg(tmp_path, ops_src, ref_src=None):
    root = tmp_path / "repro"
    pkg = root / "kernels" / "myk"
    pkg.mkdir(parents=True)
    (pkg / "ops.py").write_text(textwrap.dedent(ops_src))
    if ref_src is not None:
        (pkg / "ref.py").write_text(textwrap.dedent(ref_src))
    return pkg, root


def test_matching_kernel_pair_clean(tmp_path):
    pkg, root = _kernel_pkg(
        tmp_path,
        "def op(a, b, *, bm=8, interpret=True):\n    return a\n",
        "def op_ref(a, b, *, bm=8):\n    return a\n")
    assert check_kernel_package(pkg, root) == []


def test_missing_ref_module_flagged(tmp_path):
    pkg, root = _kernel_pkg(tmp_path, "def op(a):\n    return a\n")
    fs = check_kernel_package(pkg, root)
    assert len(fs) == 1 and "missing ref.py" in fs[0].message


def test_positional_param_mismatch_flagged(tmp_path):
    pkg, root = _kernel_pkg(
        tmp_path,
        "def op(a, b):\n    return a\n",
        "def op_ref(a):\n    return a\n")
    fs = check_kernel_package(pkg, root)
    assert len(fs) == 1 and "positional params" in fs[0].message


def test_ref_only_keyword_flagged(tmp_path):
    pkg, root = _kernel_pkg(
        tmp_path,
        "def op(a, *, bm=8):\n    return a\n",
        "def op_ref(a, *, bm=8, scale=1.0):\n    return a\n")
    fs = check_kernel_package(pkg, root)
    assert len(fs) == 1 and "ref requires keywords ['scale']" in fs[0].message


def test_unmatched_ops_flagged(tmp_path):
    pkg, root = _kernel_pkg(
        tmp_path,
        "def op(a):\n    return a\n",
        "def other_ref(a):\n    return a\n")
    fs = check_kernel_package(pkg, root)
    assert len(fs) == 1 and "no ops.py public function" in fs[0].message


# ---- baseline mechanics ---------------------------------------------------

def _finding(code, rule="no-implicit-downcast", path="repro/x/y.py"):
    return Finding(rule, path, 3, "msg", code)


def test_baseline_rejects_todo_reasons(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"findings": [
        {"rule": "x64-guard", "path": "a.py", "code": "x = 1",
         "reason": "TODO: justify this suppression"}]}))
    with pytest.raises(ValueError, match="TODO"):
        load_baseline(p)


def test_baseline_rejects_empty_reasons(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"findings": [
        {"rule": "x64-guard", "path": "a.py", "code": "x = 1",
         "reason": "  "}]}))
    with pytest.raises(ValueError, match="empty"):
        load_baseline(p)


def test_split_matches_on_whitespace_normalized_code():
    entries = [{"rule": "no-implicit-downcast", "path": "repro/x/y.py",
                "code": "x = a.astype(jnp.bfloat16)", "reason": "legacy"}]
    f = _finding("x  =  a.astype(jnp.bfloat16)")
    new, old, unused = split_baselined([f], entries)
    assert (new, old, unused) == ([], [f], [])


def test_split_reports_new_and_unused():
    entries = [{"rule": "no-implicit-downcast", "path": "repro/x/y.py",
                "code": "gone = 1", "reason": "legacy"}]
    f = _finding("x = a.astype(jnp.bfloat16)")
    new, old, unused = split_baselined([f], entries)
    assert new == [f] and old == [] and unused == entries


def test_update_baseline_preserves_reasons(tmp_path):
    p = tmp_path / "baseline.json"
    update_baseline([_finding("x = 1")], p)
    data = json.loads(p.read_text())
    assert data["findings"][0]["reason"].startswith("TODO")
    data["findings"][0]["reason"] = "a real reason"
    p.write_text(json.dumps(data))
    update_baseline([_finding("x = 1"), _finding("y = 2")], p)
    reasons = {e["code"]: e["reason"]
               for e in json.loads(p.read_text())["findings"]}
    assert reasons["x = 1"] == "a real reason"
    assert reasons["y = 2"].startswith("TODO")


# ---- the repo itself ------------------------------------------------------

def test_repo_at_head_is_clean_modulo_baseline():
    new, _old, unused = split_baselined(lint_tree(SRC_ROOT), load_baseline())
    assert new == [], "\n".join(f.render() for f in new)
    assert unused == [], "stale baseline entries: " + repr(unused)


def test_seeded_violation_in_core_engine_is_caught():
    src = (SRC_ROOT / "core" / "tile_cholesky.py").read_text()
    assert lint_source(src, "repro/core/tile_cholesky.py") == []
    seeded = src + "\n\ndef _seeded(l_kk):\n    return l_kk.astype(jnp.float32)\n"
    fs = lint_source(seeded, "repro/core/tile_cholesky.py")
    assert rules(fs) == ["no-implicit-downcast"]


# ---- CLI gate -------------------------------------------------------------

def test_check_gate_green_at_head(capsys):
    assert main(["--check"]) == 0
    assert "static analysis: OK" in capsys.readouterr().out


def test_lint_gate_fails_on_seeded_tree(tmp_path, capsys):
    bad_root = tmp_path / "repro"
    (bad_root / "core").mkdir(parents=True)
    (bad_root / "core" / "bad.py").write_text(
        "def f(a):\n    return a.astype(jnp.float32)\n")
    assert run_lint(bad_root) == 1
    assert main(["--lint-only", "--root", str(bad_root)]) == 1
    assert "no-implicit-downcast" in capsys.readouterr().out


# ---- obs-span-context -----------------------------------------------------

def test_context_managed_span_clean():
    assert lint("with obs.span('a', x=1):\n    pass\n", RUNTIME) == []


def test_context_managed_maybe_span_with_as_clean():
    assert lint("with obs.maybe_span('a', arr) as sp:\n    pass\n",
                RUNTIME) == []


def test_bare_span_call_flagged():
    fs = lint("obs.span('a', x=1)\n", RUNTIME)
    assert rules(fs) == ["obs-span-context"]
    assert "context-managed" in fs[0].message


def test_span_assigned_to_variable_flagged():
    assert rules(lint("sp = obs.maybe_span('a', arr)\n", RUNTIME)) \
        == ["obs-span-context"]


def test_enter_context_span_clean():
    assert lint("sp = stack.enter_context(obs.span('a'))\n", RUNTIME) == []


def test_span_rule_exempt_in_obs_package():
    assert lint("def span(name):\n    return _R.span(name)\n",
                "repro/obs/fixture.py") == []


def test_span_pragma_suppresses():
    src = "obs.span('a')  # repro: disable=obs-span-context -- test\n"
    assert lint(src, RUNTIME) == []


def test_variable_named_span_not_flagged():
    # a local named `span` that is never *called* is not a telemetry leak
    assert lint("span = (hi - lo) * 0.4\n", RUNTIME) == []


# ---- stale-baseline gate (PR 10) ------------------------------------------

def _stale_entry(rule="no-implicit-downcast"):
    return {"rule": rule, "path": "repro/x/gone.py",
            "code": "x = a.astype(jnp.bfloat16)", "reason": "legacy"}


def test_stale_baseline_entry_fails_check(monkeypatch, capsys):
    from repro.analysis import cli

    monkeypatch.setattr(cli, "load_baseline",
                        lambda: load_baseline() + [_stale_entry()])
    assert cli.run_lint(SRC_ROOT) == 1
    out = capsys.readouterr().out
    assert "STALE BASELINE" in out and "gone.py" in out


def test_allow_stale_baseline_downgrades_to_note(monkeypatch, capsys):
    from repro.analysis import cli

    monkeypatch.setattr(cli, "load_baseline",
                        lambda: load_baseline() + [_stale_entry()])
    assert cli.run_lint(SRC_ROOT, allow_stale=True) == 0
    out = capsys.readouterr().out
    assert "note" in out and "STALE BASELINE" not in out


def test_inactive_rule_entries_never_stale(monkeypatch):
    """A lockguard-rule entry is not stale in a lint-only run (the rule
    didn't execute), but IS stale once --concurrency runs it."""
    from repro.analysis import cli

    monkeypatch.setattr(
        cli, "load_baseline",
        lambda: load_baseline() + [_stale_entry(rule="guarded-by")])
    assert cli.run_lint(SRC_ROOT) == 0                      # rule inactive
    assert cli.run_lint(SRC_ROOT, concurrency=True) == 1    # rule active


def test_concurrency_only_cli_flags(capsys):
    assert main(["--concurrency-only", "--verbose"]) == 0
    out = capsys.readouterr().out
    assert "hb:" in out and "interleave:" in out
    assert "static analysis: OK" in out
