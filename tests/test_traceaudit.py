"""tools/traceaudit: the trace-level audit independently reproduces the
energy ledger's MVM accounting on every supported path, catches seeded
lies (extra in-loop MVM, silent f64->f32 demotion, host callbacks in the
hot loop), and pins traced structure against TRACE_BASELINE.json."""
import copy
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))     # tools/ is not on PYTHONPATH=src

from repro.core import engine  # noqa: E402
from tools.traceaudit import (  # noqa: E402
    CHECK_EVERY,
    TRACE_M,
    TRACE_N,
    PathSpec,
    _TRACE_CACHE,
    analyze_path,
    audit_paths,
    check_budget,
    check_dtype,
    check_effects,
    compare_to_baseline,
    count_mvms,
    fingerprint,
    load_baseline,
    supported_paths,
    trace_path,
)

DENSE = PathSpec("dense", "jnp", "fixed", False, True)


@pytest.fixture(scope="module")
def full_audit():
    """One full-matrix audit shared by every assertion below (tracing 34
    paths once is the expensive part; the analyzers are cheap)."""
    baseline = load_baseline()
    assert baseline is not None, \
        "TRACE_BASELINE.json missing — run --update-baseline and commit"
    records, findings, notes = audit_paths(
        supported_paths(), baseline, full_matrix=True)
    return baseline, records, findings, notes


# ------------------------------------------------------- the green path ---

def test_matrix_covers_every_axis():
    specs = supported_paths()
    names = {s.name for s in specs}
    assert len(names) == len(specs)            # names are unique ids
    assert {s.backend for s in specs} == \
        {"dense", "ell", "crossbar", "sharded"}
    assert {s.kernel for s in specs} == {"jnp", "pallas"}
    assert {s.step_rule for s in specs} == \
        {"fixed", "adaptive", "strongly_convex"}
    assert any(s.megakernel for s in specs)
    assert any(not s.restart for s in specs)
    # every backend gets a restart=False variant
    assert {s.backend for s in specs if not s.restart} == \
        {s.backend for s in specs}
    # the refinement shells are audited too (crossbar mount + the dense
    # self-mount), and only they carry the /refineN name suffix
    assert {s.refine for s in specs} == {0, 1, 2}
    assert all(("/refine" in s.name) == (s.refine > 0) for s in specs)


def test_full_matrix_is_clean(full_audit):
    _, records, findings, notes = full_audit
    assert findings == [], "\n".join(str(f) for f in findings)
    assert notes == [], notes                  # same jax version as CI
    assert len(records) == len(supported_paths())


def test_every_path_reproduces_the_ledger(full_audit):
    """The acceptance claim: traced per-window MVMs == the formula the
    energy ledger charges (times the number of analog solves on refined
    paths), and nothing MVM-shaped leaks outside beyond the refinement
    shell's counted digital residual MVMs."""
    _, records, _, _ = full_audit
    for rec in records:
        expected = (engine.refine_window_factor(rec.spec.refine)
                    * engine.mvm_window_budget(CHECK_EVERY,
                                               rec.spec.restart))
        assert rec.counts["per_window"] == expected, rec.spec.name
        assert rec.counts["outside"] == \
            engine.refine_digital_mvms(rec.spec.refine), rec.spec.name


def test_mvm_accounting_decomposes_into_window_budgets():
    """mvm_accounting == lanczos + n_windows * window_budget whenever
    iterations quantize to check_every (which every jitted path does)."""
    for ce in (1, 4, 25):
        for windows in (1, 3, 10):
            for lz in (0, 16):
                for restart in (True, False):
                    it = windows * ce
                    assert engine.mvm_accounting(it, ce, lz, restart) == \
                        lz + windows * engine.mvm_window_budget(ce, restart)


def test_trace_cache_and_fingerprint_are_deterministic():
    jx1 = trace_path(DENSE)
    assert trace_path(DENSE) is jx1            # cached by name
    fp1 = fingerprint(jx1)
    _TRACE_CACHE.pop(DENSE.name)
    fp2 = fingerprint(trace_path(DENSE))
    assert fp1 == fp2                          # stable across retrace


# ----------------------------------------------------------- seeded lies ---

def _K():
    # built inside the traced fns, where trace_path has x64 enabled
    return jnp.ones((TRACE_M, TRACE_N), jnp.float64)


def _lying_operator():
    """A dense operator whose fwd sneaks in a SECOND operator MVM the
    ledger never charges (make_jaxpr does no CSE, so both dots stay)."""

    def fwd(v, key=None):
        K = _K()
        w = K @ v
        w2 = K @ (2.0 * v)       # the unledgered extra device read
        return w + 0.0 * w2

    def adj(v, key=None):
        return _K().T @ v

    return engine.Operator(fwd, adj, "dense")


def test_budget_checker_catches_extra_in_loop_mvm():
    jaxpr = trace_path(DENSE, operator_override=_lying_operator())
    counts = count_mvms(jaxpr)
    findings = check_budget(DENSE, counts)
    assert findings, "seeded extra MVM went undetected"
    assert any("mvm_window_budget" in f.message for f in findings)
    # fwd runs check_every times stepping + 2x at the check: +6 MVMs
    assert counts["per_window"] == \
        engine.mvm_window_budget(CHECK_EVERY, True) + CHECK_EVERY + 2


def _demoting_operator():
    """fwd computes in f32 and silently casts back up — the classic
    'works on CPU, wrong answer on the device' demotion."""

    def fwd(v, key=None):
        w32 = _K().astype(jnp.float32) @ v.astype(jnp.float32)
        return w32.astype(jnp.float64)

    def adj(v, key=None):
        return _K().T @ v

    return engine.Operator(fwd, adj, "dense")


def test_dtype_checker_catches_f64_to_f32_demotion():
    jaxpr = trace_path(DENSE, operator_override=_demoting_operator())
    findings = check_dtype(DENSE.name, jaxpr)
    assert findings, "seeded f64->f32 demotion went undetected"
    assert any("narrowing" in f.message for f in findings)
    # the clean trace of the same path carries no dtype findings
    assert check_dtype(DENSE.name, trace_path(DENSE)) == []


def _chatty_operator():
    import jax

    def fwd(v, key=None):
        jax.debug.print("fwd norm {x}", x=jnp.sum(v))
        return _K() @ v

    def adj(v, key=None):
        return _K().T @ v

    return engine.Operator(fwd, adj, "dense")


def test_effects_checker_catches_callback_in_hot_loop():
    jaxpr = trace_path(DENSE, operator_override=_chatty_operator())
    findings = check_effects(DENSE.name, jaxpr)
    assert findings, "seeded in-loop host callback went undetected"
    assert any("hot loop" in f.message for f in findings)
    assert check_effects(DENSE.name, trace_path(DENSE)) == []


# ---------------------------------------------------------- the baseline ---

def _records(full_audit):
    return full_audit[1]


def test_baseline_drift_reports_primitive_diff(full_audit):
    baseline, records = full_audit[0], _records(full_audit)
    bad = copy.deepcopy(baseline)
    name = records[0].spec.name
    bad["paths"][name]["fingerprint"] = "0" * 64
    bad["paths"][name]["primitives"]["dot_general"] = 999.0
    findings, notes = compare_to_baseline(records, bad, full_matrix=True)
    assert notes == []
    assert len(findings) == 1 and findings[0].path == name
    assert "drifted" in findings[0].message
    assert "dot_general: 999 ->" in findings[0].message   # human diff
    assert "--update-baseline" in findings[0].message


def test_baseline_missing_and_stale_entries(full_audit):
    baseline, records = full_audit[0], _records(full_audit)
    bad = copy.deepcopy(baseline)
    victim = records[0].spec.name
    del bad["paths"][victim]
    bad["paths"]["dense/jnp/fixed/mega9/restart1"] = \
        {"fingerprint": "x", "mvms": {}, "primitives": {}}
    findings, _ = compare_to_baseline(records, bad, full_matrix=True)
    msgs = {f.path: f.message for f in findings}
    assert "missing from TRACE_BASELINE.json" in msgs[victim]
    assert "stale" in msgs["dense/jnp/fixed/mega9/restart1"]
    # a filtered run must NOT judge completeness
    findings, _ = compare_to_baseline(records, bad, full_matrix=False)
    assert all("stale" not in f.message for f in findings)


def test_version_skew_downgrades_fingerprints_to_notes(full_audit):
    baseline, records = full_audit[0], _records(full_audit)
    skew = copy.deepcopy(baseline)
    skew["jax_version"] = "0.0.0"
    skew["paths"][records[0].spec.name]["fingerprint"] = "0" * 64
    findings, notes = compare_to_baseline(records, skew, full_matrix=True)
    assert findings == []                      # soft under version skew
    assert any("0.0.0" in n for n in notes)
    assert any("drifted" in n for n in notes)


def test_adaptive_traces_identical_mvm_budget_to_fixed(full_audit):
    """PR 8's zero-extra-MVM claim, per family, from the traces."""
    _, records, _, _ = full_audit
    by_family = {}
    for rec in records:
        s = rec.spec
        fam = (s.backend, s.kernel, s.megakernel, s.restart, s.refine)
        by_family.setdefault(fam, {})[s.step_rule] = rec
    checked = 0
    for rules in by_family.values():
        if "fixed" in rules and "adaptive" in rules:
            assert rules["adaptive"].counts == rules["fixed"].counts
            checked += 1
    assert checked >= 8    # every backend x kernel (x mega) family


# ------------------------------------------------------------------ CLI ---

def test_cli_list_and_filtered_run(capsys):
    from tools.traceaudit.__main__ import main
    assert main(["--list-paths"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert len(out) == len(supported_paths())

    assert main(["--paths", "dense/jnp/fixed", "--json"]) == 0
    assert json.loads(capsys.readouterr().out) == []


def test_cli_json_reports_findings(tmp_path, capsys):
    from tools.traceaudit.__main__ import main
    baseline = copy.deepcopy(load_baseline())
    name = "dense/jnp/fixed/mega0/restart1"
    baseline["paths"][name]["fingerprint"] = "0" * 64
    bad = tmp_path / "baseline.json"
    bad.write_text(json.dumps(baseline))
    diff = tmp_path / "diff.txt"
    rc = main(["--paths", name, "--json", "--baseline", str(bad),
               "--diff-out", str(diff)])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert [e["rule"] for e in payload] == ["fingerprint"]
    assert payload[0]["file"] == name
    assert "drifted" in diff.read_text()       # the CI artifact


def test_analyze_path_matches_committed_baseline_entry(full_audit):
    """Spot-check the baseline file content against a live record."""
    baseline, records = full_audit[0], _records(full_audit)
    assert baseline["schema"] == "traceaudit/v1"
    assert baseline["trace_shape"] == [TRACE_M, TRACE_N]
    rec = records[0]
    entry = baseline["paths"][rec.spec.name]
    assert entry["fingerprint"] == rec.fingerprint
    assert entry["mvms"] == rec.counts
    assert entry["primitives"] == \
        {k: rec.histogram[k] for k in sorted(rec.histogram)}
    fresh = analyze_path(rec.spec, trace_path(rec.spec))
    assert fresh.fingerprint == rec.fingerprint
