"""Tests for repro.analysis: fixture catches, self-scan, model checker.

The analyzer is pure-AST (no jax import needed at analysis time), so these
tests are fast — the heaviest item is the exhaustive staleness model check.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys

import pytest

from repro.analysis import (analyze_paths, extract_bound_model,
                            extract_bound_model_from_source,
                            extract_enforcement, model_check)
from repro.analysis.staleness_check import ExtractionError

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "analysis_fixtures")
SRC = os.path.join(REPO, "src", "repro")

FAMILIES = ("recompile", "rng", "collectives", "pytree", "pallas",
            "callbacks")


def _expected_violations(path):
    out = []
    with open(path, encoding="utf-8") as fh:
        for ln, line in enumerate(fh, 1):
            m = re.search(r"# VIOLATION: ([\w-]+)", line)
            if m:
                out.append((ln, m.group(1)))
    return sorted(out)


@pytest.mark.parametrize("family", FAMILIES)
def test_bad_fixture_caught(family):
    """Every marked violation is reported with its exact rule id + line."""
    path = os.path.join(FIXTURES, f"bad_{family}.py")
    expected = _expected_violations(path)
    assert expected, f"fixture {path} carries no VIOLATION markers"
    got = sorted((f.line, f.rule)
                 for f in analyze_paths([path], model_check=False))
    assert got == expected


@pytest.mark.parametrize("family", FAMILIES)
def test_good_fixture_clean(family):
    """The clean counterpart of each family produces zero findings."""
    path = os.path.join(FIXTURES, f"good_{family}.py")
    findings = analyze_paths([path], model_check=False)
    assert findings == [], [str(f) for f in findings]


def test_suppression_comment():
    """An inline reasoned ignore silences exactly its rule on its line."""
    path = os.path.join(FIXTURES, f"bad_rng.py")
    src = open(path, encoding="utf-8").read()
    patched = src.replace(
        "# VIOLATION: rng-reuse",
        "# analysis: ignore[rng-reuse] -- fixture", 1)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "patched.py")
        with open(p, "w", encoding="utf-8") as fh:
            fh.write(patched)
        findings = analyze_paths([p], model_check=False)
        rules = sorted((f.rule) for f in findings)
        assert rules == ["rng-reuse", "rng-reuse"]  # 3 - 1 suppressed
        # strict mode rejects reason-less ignores
        bare = patched.replace("-- fixture", "")
        with open(p, "w", encoding="utf-8") as fh:
            fh.write(bare)
        strict = analyze_paths([p], strict=True, model_check=False)
        assert any(f.rule == "bare-ignore" for f in strict)


def test_self_scan_clean():
    """src/repro is violation-free (modulo inline reasoned ignores)."""
    findings = analyze_paths([SRC], strict=True)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exit_codes():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    clean = subprocess.run(
        [sys.executable, "-m", "repro.analysis", SRC, "--strict"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    dirty = subprocess.run(
        [sys.executable, "-m", "repro.analysis",
         os.path.join(FIXTURES, "bad_rng.py"), "--no-model-check"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert dirty.returncode == 1
    assert "rng-reuse" in dirty.stdout
    rules = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list-rules"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert rules.returncode == 0
    for rule_id in ("traced-branch", "rng-reuse", "unmasked-gather",
                    "pytree-frozen", "pallas-ref", "host-callback",
                    "staleness-contract"):
        assert rule_id in rules.stdout


# ------------------------------------------------------------------ model


PRODUCERS = [
    ("core/ps.py", os.path.join(SRC, "core", "ps.py")),
    ("psrun/runtime.py", os.path.join(SRC, "psrun", "runtime.py")),
    ("pods/runtime.py", os.path.join(SRC, "pods", "runtime.py")),
]


def test_bound_extraction_matches_declared_algebra():
    bm = extract_bound_model(os.path.join(SRC, "core", "delays.py"))
    for s in range(3):
        for sx in range(3):
            for agg in (1, 2, 3):
                assert bm.bound("intra", s, sx, agg) == s
                assert bm.bound("xpod", s, sx, agg) == s + sx
                assert bm.bound("xpod-wired", s, sx, agg) \
                    == s + sx + agg - 1


@pytest.mark.parametrize(("producer", "path"), PRODUCERS,
                         ids=[p for p, _ in PRODUCERS])
def test_model_check_verifies_producer(producer, path):
    """The exhaustive small-config grid finds no contract violation in the
    policy of ``core/ps.py``, the psrun runtime is recognized as calling
    it, and the pods runtime as running the psrun body."""
    bm = extract_bound_model(os.path.join(SRC, "core", "delays.py"))
    enf = extract_enforcement(path, producer)
    assert enf.trigger_offset == 1
    assert enf.refresh_lag == 1
    assert enf.xpod_refresh_shipped
    assert enf.delivery_shipped
    assert enf.delegate == {"core/ps.py": None,
                            "psrun/runtime.py": "core/ps.py",
                            "pods/runtime.py": "psrun/runtime.py"}[producer]
    ces = model_check(bm, enf)
    assert ces == [], "\n".join(str(c) for c in ces)


def test_model_check_detects_widening_mutant():
    """An off-by-one in the widening (`agg_clocks - 2`) is caught: the
    post-refresh shipment lag on the wired cross-pod channel exceeds the
    (mutated) bound, so the checker must produce counterexamples."""
    src = open(os.path.join(SRC, "core", "delays.py"),
               encoding="utf-8").read()
    mutant = src.replace("(cfg.agg_clocks - 1)", "(cfg.agg_clocks - 2)")
    assert mutant != src, "widening expression not found to mutate"
    bm = extract_bound_model_from_source(mutant)
    enf = extract_enforcement(os.path.join(SRC, "core", "ps.py"),
                              "core/ps.py")
    ces = model_check(bm, enf)
    assert ces, "mutant bound not detected"
    # the faulted cross-pod channel shares the agg widening, so the
    # mutant now falls on both wired channels — but nowhere else
    chans = {c.channel for c in ces}
    assert "xpod-wired" in chans
    assert chans <= {"xpod-wired", "xpod-faulted"}
    # and the un-mutated bound still verifies on the same extraction
    assert model_check(extract_bound_model_from_source(src), enf) == []


def test_model_check_detects_retry_budget_mutant():
    """An off-by-one in the lossy-wire widening (`retry_budget - 1`) is
    refuted: two flight windows stack (ship gating reads start-of-clock
    lane idleness), so the full ``2 * flight_budget`` is exactly tight —
    counterexamples must land on the faulted channel and only there."""
    src = open(os.path.join(SRC, "core", "delays.py"),
               encoding="utf-8").read()
    mutant = src.replace("+ retry_budget", "+ (retry_budget - 1)")
    assert mutant != src, "retry_budget widening not found to mutate"
    bm = extract_bound_model_from_source(mutant)
    enf = extract_enforcement(os.path.join(SRC, "core", "ps.py"),
                              "core/ps.py")
    ces = model_check(bm, enf)
    assert ces, "retry_budget mutant not detected"
    # the same mutated expression also evaluates at retry_budget=0 on
    # the plain wired channel (where it degenerates to the agg - 2
    # mutant); the new evidence is the faulted-channel refutation at
    # flight >= 1, which exercises the two-flight-window stacking
    faulted = [c for c in ces if c.channel == "xpod-faulted"]
    assert faulted, "no counterexample on the faulted channel"
    # the grid breaks per config at the first failing flight (0 here,
    # where the mutant degenerates to agg - 2); pin the nonzero-flight
    # tightness directly: at flight=1 the mutant bound (2F - 1) is one
    # short of the stacked two-window worst case, the true bound holds
    from repro.analysis.staleness_check import check_channel_faulted

    good = extract_bound_model_from_source(src)
    config = (12, 4, 0, 0, 1)      # (T, P, s, s_xpod, agg): tight corner
    assert check_channel_faulted(bm, enf, config, flight=1) is not None
    assert check_channel_faulted(good, enf, config, flight=1) is None
    # and the un-mutated bound still verifies on the same extraction
    assert model_check(good, enf) == []


def test_faulted_extraction_requires_wire_tip_caps():
    """The shared policy caps faulted refresh/delivery on ``wire_tip``,
    and the runtime that calls it inherits the caps; a policy that drops
    either cap must fail extraction loudly (the cap guards against
    reading unarrived ring content, which the staleness lag invariant
    alone cannot observe)."""
    from repro.analysis import extract_enforcement_from_source

    enf = extract_enforcement(os.path.join(SRC, "psrun", "runtime.py"),
                              "psrun/runtime.py")
    assert enf.xpod_refresh_capped and enf.delivery_capped
    src = open(os.path.join(SRC, "core", "ps.py"), encoding="utf-8").read()
    enf = extract_enforcement_from_source(src, "core/ps.py")
    assert enf.xpod_refresh_capped and enf.delivery_capped
    uncapped = src.replace('cst["wire_tip"]', 'cst["pend_clock"]')
    assert uncapped != src
    with pytest.raises(ExtractionError):
        extract_enforcement_from_source(uncapped, "core/ps.py")


def test_extraction_is_brittle_on_drift():
    """If a producer's enforcement pattern drifts, extraction fails loudly
    rather than silently verifying stale algebra."""
    src = open(os.path.join(SRC, "core", "ps.py"), encoding="utf-8").read()
    drifted = src.replace("forced = cview < (c - s_eff - 1)",
                          "forced = cview <= (c - s_eff - 1)")
    assert drifted != src
    from repro.analysis import extract_enforcement_from_source
    with pytest.raises(ExtractionError):
        extract_enforcement_from_source(drifted, "core/ps.py")


def test_extraction_refuses_a_trigger_beside_the_shared_call():
    """The runtime delegates its enforcement to ``core/ps.py``; a runtime
    that also fires a trigger of its own beside the shared call fails
    extraction, since the checker would then verify code the runtime
    does not run alone."""
    src = open(os.path.join(SRC, "psrun", "runtime.py"),
               encoding="utf-8").read()
    call = "cview, forced, staleness, intransit_inf = enforce("
    assert src.count(call) == 1
    indent = src.split(call)[0].rsplit("\n", 1)[1]
    pasted = src.replace(
        call, f"forced = cview < (c - s_eff - 1)\n{indent}{call}")
    from repro.analysis import extract_enforcement_from_source
    assert extract_enforcement_from_source(
        src, "psrun/runtime.py").delegate == "core/ps.py"
    with pytest.raises(ExtractionError, match="re-implements"):
        extract_enforcement_from_source(pasted, "psrun/runtime.py")


def test_model_check_covers_churn_outages():
    """Dead-reader windows are part of the grid: freezing cview during an
    outage and forcing on rejoin stays within bound (and a refresh that
    failed to fire on rejoin would be caught)."""
    bm = extract_bound_model(os.path.join(SRC, "core", "delays.py"))
    enf = extract_enforcement(os.path.join(SRC, "psrun", "runtime.py"),
                              "psrun/runtime.py")
    assert model_check(bm, enf, churn=True) == []
    # sanity: the adversary space is non-trivial — with a broken refresh
    # (refresh to c - 3 instead of c - 1) the bound must break
    import dataclasses
    broken = dataclasses.replace(enf, refresh_lag=3)
    assert model_check(bm, broken), \
        "checker failed to refute a lagging refresh"
