"""Tests for the `repro.analysis` passes (ISSUE 8).

Covers the AST lint rules on synthetic packages (seeded violations,
waivers, key stability), the jaxpr invariant checks on seeded jaxprs
(gather budget, f64, transfer, donation), the committed-baseline
workflow, and the `scripts/analyze.py` CLI exit codes the CI gate
relies on."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (Finding, check_donation, check_invariants,
                            diff_baseline, load_baseline, run_ast_lint,
                            run_jaxpr_checks, save_baseline)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_pkg(tmp_path, source, name="mod"):
    """Write a one-module `repro` package under tmp and return its src
    root (what run_ast_lint / analyze.py --src take)."""
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / f"{name}.py").write_text(textwrap.dedent(source))
    return str(tmp_path / "src")


def _gating(findings):
    return [f for f in findings if f.severity != "info"]


# ---------------------------------------------------------------------------
# AST lint rules
# ---------------------------------------------------------------------------

def test_item_in_jitted_fn_is_hot_path_error(tmp_path):
    src = _mk_pkg(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x.item()
    """)
    fs = _gating(run_ast_lint(src)[0])
    assert [f.rule for f in fs] == ["host-sync"]
    assert fs[0].severity == "error"
    assert "item" in fs[0].detail


def test_sync_reachable_through_helper_is_flagged(tmp_path):
    src = _mk_pkg(tmp_path, """
        import jax
        import numpy as np

        def helper(x):
            return np.asarray(x)

        @jax.jit
        def f(x):
            return helper(x)
    """)
    fs = _gating(run_ast_lint(src)[0])
    assert [f.rule for f in fs] == ["host-sync"]
    assert fs[0].symbol.endswith("helper")


def test_waiver_comment_suppresses(tmp_path):
    src = _mk_pkg(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x.item()  # analysis: ok(host-sync)
    """)
    assert _gating(run_ast_lint(src)[0]) == []


def test_host_sync_outside_trace_is_info_only(tmp_path):
    src = _mk_pkg(tmp_path, """
        import numpy as np

        def host_fn(x):
            return np.asarray(x)
    """)
    fs, _ = run_ast_lint(src)
    assert [f.rule for f in fs] == ["sync-site"]
    assert fs[0].severity == "info"


def test_host_rng_and_time_under_trace(tmp_path):
    src = _mk_pkg(tmp_path, """
        import jax
        import random
        import time

        @jax.jit
        def f(x):
            return x * random.random() + time.time()
    """)
    fs = _gating(run_ast_lint(src)[0])
    assert sorted(f.rule for f in fs) == ["host-rng-under-trace"] * 2


def test_jax_random_is_not_host_rng(tmp_path):
    src = _mk_pkg(tmp_path, """
        import jax

        @jax.jit
        def f(key, x):
            return x + jax.random.normal(key, x.shape)
    """)
    assert _gating(run_ast_lint(src)[0]) == []


def test_mutable_default_error_and_call_default_warn(tmp_path):
    src = _mk_pkg(tmp_path, """
        def f(x, acc=[]):
            acc.append(x)
            return acc

        def g(x, policy=dict()):
            return policy
    """)
    fs = _gating(run_ast_lint(src)[0])
    assert {f.rule for f in fs} == {"mutable-default"}
    assert sorted(f.severity for f in fs) == ["error", "warn"]


def test_allocator_free_flagged_decref_ok(tmp_path):
    src = _mk_pkg(tmp_path, """
        def release(table, page):
            table.allocator.free(page)

        def release_ok(table, page):
            table.allocator.decref(page)
    """)
    fs = _gating(run_ast_lint(src)[0])
    assert [f.rule for f in fs] == ["allocator-free"]
    assert fs[0].symbol.endswith("release")


def test_jit_static_args_literal_call(tmp_path):
    src = _mk_pkg(tmp_path, """
        import jax

        def run(x):
            f = jax.jit(lambda v, mode: v)
            return f(x, "fast")
    """)
    fs = _gating(run_ast_lint(src)[0])
    assert [f.rule for f in fs] == ["jit-static-args"]


def test_finding_keys_stable_across_line_churn(tmp_path):
    body = """
        import jax

        @jax.jit
        def f(x):
            return x.item()
    """
    keys1 = {f.key for f in _gating(run_ast_lint(_mk_pkg(tmp_path, body))[0])}
    churned = "\n\n\n# a comment\n" + textwrap.dedent(body)
    (tmp_path / "src" / "repro" / "mod.py").write_text(churned)
    keys2 = {f.key for f in _gating(run_ast_lint(str(tmp_path / "src"))[0])}
    assert keys1 == keys2


# ---------------------------------------------------------------------------
# baseline workflow
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_diff(tmp_path):
    old = Finding("step-sync", "a.py", 3, "Engine.step", "np.asarray#0",
                  "m", "warn")
    gone = Finding("step-sync", "b.py", 9, "old_fn", "item#0", "m", "warn")
    path = str(tmp_path / "baseline.json")
    save_baseline(path, [old, gone])
    bl = load_baseline(path)
    assert set(bl) == {old.key, gone.key}
    fresh = Finding("host-sync", "c.py", 1, "f", "item#0", "m", "error")
    new, grand, fixed = diff_baseline([old, fresh], bl)
    assert [f.key for f in new] == [fresh.key]
    assert [f.key for f in grand] == [old.key]
    assert fixed == [gone.key]


def test_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == {}


def test_baseline_version_mismatch_raises(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"version": 999, "findings": {}}))
    with pytest.raises(ValueError, match="version"):
        load_baseline(str(p))


def test_info_findings_never_baselined(tmp_path):
    info = Finding("sync-site", "a.py", 1, "f", "np.asarray#0", "m", "info")
    path = str(tmp_path / "b.json")
    save_baseline(path, [info])
    assert load_baseline(path) == {}


# ---------------------------------------------------------------------------
# jaxpr invariants (seeded violations)
# ---------------------------------------------------------------------------

def test_seeded_gather_over_budget():
    def f(x, idx):
        return x[idx] + x[idx * 2]           # two gathers

    closed = jax.make_jaxpr(f)(jnp.ones((8, 4)), jnp.asarray([1, 2]))
    fs = check_invariants(closed, name="fixture", gather_budget=1)
    assert [f.rule for f in fs] == ["jaxpr-gather-budget"]
    assert check_invariants(closed, name="fixture", gather_budget=2) == []


def test_seeded_f64_detected():
    with jax.enable_x64():
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones((4,)))
    fs = check_invariants(closed, name="fixture")
    assert "jaxpr-f64" in {f.rule for f in fs}


def test_seeded_transfer_detected():
    closed = jax.make_jaxpr(
        lambda x: jax.device_put(x) + 1.0)(jnp.ones((4,)))
    fs = check_invariants(closed, name="fixture")
    assert "jaxpr-transfer" in {f.rule for f in fs}


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_donation_drop_detected():
    # output shape differs from the donated buffer: XLA cannot alias
    bad = jax.jit(lambda x: x[:2], donate_argnums=(0,))
    fs = check_donation(bad, (jnp.ones((4,)),), name="fix", min_aliases=1)
    assert [f.rule for f in fs] == ["jaxpr-donation"]
    good = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    assert check_donation(good, (jnp.ones((4,)),), name="fix",
                          min_aliases=1) == []


def test_registered_entry_points_clean():
    """The real serving/kernel entry points satisfy every invariant —
    budgets in jaxpr_check's docstring, donation of the KV pool."""
    assert run_jaxpr_checks() == []


# ---------------------------------------------------------------------------
# repo tree + CLI gate
# ---------------------------------------------------------------------------

def test_repo_tree_has_no_new_findings():
    findings, graph = run_ast_lint(os.path.join(ROOT, "src"))
    assert not [f for f in findings if f.severity == "error"]
    baseline = load_baseline(os.path.join(ROOT, "analysis/baseline.json"))
    new, _grand, _fixed = diff_baseline(findings, baseline)
    assert new == []
    # the serving entry points must actually be in the traced set —
    # an import-graph regression would silently blind the linter
    assert any(q.endswith("Model.decode_paged") for q in graph.traced)
    assert any(q.endswith("Engine._decode_step") for q in graph.step_loop)


def _run_cli(args, cwd=ROOT):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts/analyze.py"),
         "--no-jaxpr", *args],
        capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_exit_zero_on_committed_baseline():
    r = _run_cli(["--baseline", os.path.join(ROOT,
                                             "analysis/baseline.json")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_cli_exit_nonzero_on_seeded_violation(tmp_path):
    src = _mk_pkg(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x.item()
    """)
    r = _run_cli(["--src", src,
                  "--baseline", str(tmp_path / "empty.json")])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "NEW finding" in r.stdout
    assert "host-sync" in r.stdout
