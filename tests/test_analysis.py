"""Tests for repro.analysis — the static contract linter.

Each rule gets three fixtures: a violating tree (true positive), a clean
tree (no false positive), and a suppressed variant (inline allow). Fixture
trees carry their own minimal registries (``core/telemetry.py``,
``obs/trace.py``, ``runtime/validate.py``) so the analyzer resolves them
exactly like the real package. On top of that: a no-new-findings run over
the real ``src/repro``, a baseline round-trip, and the CLI gate driven via
subprocess (what CI actually runs).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import Finding, all_rule_ids, run_analysis
from repro.analysis.findings import load_baseline, save_baseline

REAL_ROOT = Path(__file__).resolve().parents[1] / "src" / "repro"
REAL_BASELINE = Path(__file__).resolve().parents[1] / "analysis" / "baseline.json"


def make_tree(tmp_path: Path, files: dict) -> Path:
    root = tmp_path / "pkg"
    for rel, content in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    return root


# Minimal registries a fixture tree needs so rules resolve against it.
REGISTRIES = {
    "core/telemetry.py": """
        from collections import Counter

        KEY_FAMILIES = {
            "fallback": ("fault:{}->{}", "nan_guard:rerun"),
            "breaker": ("{}:open", "{}:close"),
        }

        FALLBACK_COUNTS = Counter()
        BREAKER_COUNTS = Counter()


        def reset_fallback_counts():
            FALLBACK_COUNTS.clear()


        def reset_breaker_counts():
            BREAKER_COUNTS.clear()


        ALL_COUNTERS = {
            "fallback": FALLBACK_COUNTS,
            "breaker": BREAKER_COUNTS,
        }

        _RESETS = (reset_fallback_counts, reset_breaker_counts)
    """,
    "obs/trace.py": """
        SPAN_NAMES = frozenset({"plan.build", "numeric.dispatch"})


        def span(name, **attrs):
            return None
    """,
    "runtime/validate.py": """
        class SpgemmError(Exception):
            pass


        class SpgemmConfigError(SpgemmError, ValueError):
            pass
    """,
}


def run_on(tmp_path, files, rules=None):
    root = make_tree(tmp_path, {**REGISTRIES, **files})
    return run_analysis(root, rules=rules)


def codes(findings):
    return sorted(f.code for f in findings)


# --------------------------------------------------------------------------
# rule registry / plumbing
# --------------------------------------------------------------------------


def test_all_five_rules_registered():
    assert all_rule_ids() == ["env", "jit-boundary", "span", "taxonomy",
                              "telemetry-key"]


def test_registries_alone_are_clean(tmp_path):
    report = run_on(tmp_path, {})
    assert report.ok, codes(report.new)
    assert not report.suppressed and not report.baselined


def test_unknown_rule_is_a_loud_error(tmp_path):
    with pytest.raises(KeyError, match="nope"):
        run_on(tmp_path, {}, rules=["nope"])


def test_syntax_error_fails_the_gate(tmp_path):
    report = run_on(tmp_path, {"broken.py": "def f(:\n"})
    assert not report.ok
    assert codes(report.new) == ["parse.syntax-error"]


# --------------------------------------------------------------------------
# rule 1: jit-boundary
# --------------------------------------------------------------------------


def test_jit_try_in_traced_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        import jax


        @jax.jit
        def f(x):
            try:
                return x + 1
            except Exception:
                return x
    """}, rules=["jit-boundary"])
    assert "jit-boundary.try-in-traced" in codes(report.new)


def test_jit_host_sync_in_traced_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        import jax
        import numpy as np


        def helper(x):
            return np.asarray(x)


        def f(x):
            return helper(x) + float(x[0])


        g = jax.jit(f)
    """}, rules=["jit-boundary"])
    # both the direct float() in f and the np.asarray in its callee helper
    assert codes(report.new).count("jit-boundary.host-sync") == 2


def test_jit_silent_catch_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        def run_cell(cell):
            return cell.lower().compile()


        def survey(cells):
            out = []
            for c in cells:
                try:
                    out.append(run_cell(c))
                except Exception:
                    pass
            return out
    """}, rules=["jit-boundary"])
    assert "jit-boundary.silent-catch" in codes(report.new)


def test_jit_clean_ladder_passes(tmp_path):
    # catching OUTSIDE jit with a typed re-raise is the sanctioned ladder
    report = run_on(tmp_path, {"mod.py": """
        import jax
        from runtime.validate import SpgemmConfigError


        @jax.jit
        def f(x):
            return x + 1


        def dispatch(x):
            try:
                return f(x)
            except Exception as e:
                raise SpgemmConfigError("kernel failed") from e
    """}, rules=["jit-boundary"])
    assert report.ok, codes(report.new)


def test_jit_suppressed(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        import jax


        @jax.jit
        def f(x):
            # repro: allow[jit-boundary] fixture-sanctioned
            try:
                return x + 1
            except Exception:
                return x
    """}, rules=["jit-boundary"])
    assert report.ok
    assert codes(report.suppressed) == ["jit-boundary.try-in-traced"]


# --------------------------------------------------------------------------
# rule 2: telemetry-key
# --------------------------------------------------------------------------


def test_key_grammar_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from core.telemetry import FALLBACK_COUNTS


        def hop():
            FALLBACK_COUNTS["nan_guard:typo"] += 1
    """}, rules=["telemetry-key"])
    assert codes(report.new) == ["telemetry-key.grammar"]


def test_key_param_expansion_violating(tmp_path):
    # the f-string key itself is fine ({}:open / {}:close), but a call site
    # passes an event outside the grammar — caught through param expansion
    report = run_on(tmp_path, {"mod.py": """
        from core.telemetry import BREAKER_COUNTS


        class Breaker:
            def __init__(self, name):
                self.name = name

            def _count(self, event):
                BREAKER_COUNTS[f"{self.name}:{event}"] += 1

            def trip(self):
                self._count("explode")
    """}, rules=["telemetry-key"])
    assert codes(report.new) == ["telemetry-key.grammar"]
    assert "explode" in report.new[0].message


def test_key_clean_literals_and_fstrings(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from core.telemetry import BREAKER_COUNTS, FALLBACK_COUNTS


        def hop(a, b):
            FALLBACK_COUNTS["nan_guard:rerun"] += 1
            FALLBACK_COUNTS[f"fault:{a}->{b}"] += 1


        class Breaker:
            def __init__(self, name):
                self.name = name

            def _count(self, event):
                BREAKER_COUNTS[f"{self.name}:{event}"] += 1

            def trip(self):
                self._count("open")
                self._count("close")
    """}, rules=["telemetry-key"])
    assert report.ok, codes(report.new)


def test_key_unregistered_counter_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from collections import Counter

        ROGUE_COUNTS = Counter()
    """}, rules=["telemetry-key"])
    assert codes(report.new) == ["telemetry-key.unregistered"]


def test_key_suppressed(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from core.telemetry import FALLBACK_COUNTS


        def hop():
            # repro: allow[telemetry-key] fixture-sanctioned
            FALLBACK_COUNTS["nan_guard:typo"] += 1
    """}, rules=["telemetry-key"])
    assert report.ok
    assert codes(report.suppressed) == ["telemetry-key.grammar"]


# --------------------------------------------------------------------------
# rule 3: taxonomy
# --------------------------------------------------------------------------


def test_taxonomy_bare_raise_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        def f(x):
            if x < 0:
                raise ValueError("negative")
            if x > 10:
                raise RuntimeError("too big")
    """}, rules=["taxonomy"])
    assert codes(report.new) == ["taxonomy.bare-raise", "taxonomy.bare-raise"]


def test_taxonomy_broad_except_swallow_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        def f(x):
            try:
                return x.go()
            except Exception:
                return None
    """}, rules=["taxonomy"])
    assert codes(report.new) == ["taxonomy.broad-except"]


def test_taxonomy_clean(tmp_path):
    # typed raises are fine anywhere; validate.py itself may raise bare;
    # a broad except that re-raises typed or records telemetry is loud
    report = run_on(tmp_path, {
        "runtime/validate.py": REGISTRIES["runtime/validate.py"] + """

        def resolve(mode):
            if mode not in ("off", "on"):
                raise ValueError(mode)
    """,
        "mod.py": """
        from core.telemetry import FALLBACK_COUNTS
        from runtime.validate import SpgemmConfigError


        def f(x):
            if x < 0:
                raise SpgemmConfigError("negative")
            try:
                return x.go()
            except Exception as e:
                raise SpgemmConfigError("failed") from e


        def g(x, a, b):
            try:
                return x.go()
            except Exception:
                FALLBACK_COUNTS[f"fault:{a}->{b}"] += 1
                return None
    """}, rules=["taxonomy"])
    assert report.ok, codes(report.new)


def test_taxonomy_suppressed(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        def f(x):
            # repro: allow[taxonomy] fixture-sanctioned
            raise ValueError("negative")
    """}, rules=["taxonomy"])
    assert report.ok
    assert codes(report.suppressed) == ["taxonomy.bare-raise"]


# --------------------------------------------------------------------------
# rule 4: span
# --------------------------------------------------------------------------


def test_span_unknown_name_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from obs.trace import span


        def f():
            with span("plan.bulid"):
                pass
    """}, rules=["span"])
    assert codes(report.new) == ["span.unknown-name"]
    assert "plan.bulid" in report.new[0].message


def test_span_dynamic_name_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from obs.trace import span


        def f(name):
            with span(name):
                pass
    """}, rules=["span"])
    assert codes(report.new) == ["span.dynamic-name"]


def test_span_clean_and_missing_registry(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from obs.trace import span


        def f():
            with span("plan.build", structure_key="k1"):
                pass
    """}, rules=["span"])
    assert report.ok, codes(report.new)

    # a trace module without SPAN_NAMES is itself a finding
    report = run_on(tmp_path / "nr", {
        "obs/trace.py": "def span(name):\n    return None\n",
        "mod.py": "from obs.trace import span\n\n\ndef f():\n"
                  "    return span('anything')\n",
    }, rules=["span"])
    assert codes(report.new) == ["span.no-registry"]


def test_span_suppressed(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        from obs.trace import span


        def f():
            # repro: allow[span] fixture-sanctioned
            with span("plan.bulid"):
                pass
    """}, rules=["span"])
    assert report.ok
    assert codes(report.suppressed) == ["span.unknown-name"]


# --------------------------------------------------------------------------
# rule 5: env
# --------------------------------------------------------------------------


def test_env_import_time_mutation_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        import os

        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    """}, rules=["env"])
    assert codes(report.new) == ["env.import-time-mutation"]


def test_env_unsanctioned_read_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        import os


        def knob():
            return os.environ.get("REPRO_SECRET_KNOB", "off")
    """}, rules=["env"])
    assert codes(report.new) == ["env.unsanctioned-read"]


def test_env_import_time_device_work_violating(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        import jax

        N_DEVICES = jax.device_count()
    """}, rules=["env"])
    assert codes(report.new) == ["env.import-time-device-work"]


def test_env_clean(tmp_path):
    # sanctioned read site, function-scoped write, main-guard entrypoint
    report = run_on(tmp_path, {
        "runtime/validate.py": REGISTRIES["runtime/validate.py"] + """

        import os


        def resolve_mode(mode):
            if mode is None:
                return os.environ.get("REPRO_VALIDATE", "off")
            return mode
    """,
        "launch/dryrun.py": """
        import os


        def force_host_devices(n=512):
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={n}")


        def main():
            pass


        if __name__ == "__main__":
            force_host_devices()
            main()
    """}, rules=["env"])
    assert report.ok, codes(report.new)


def test_env_suppressed(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        import os

        # repro: allow[env] fixture-sanctioned
        os.environ["XLA_FLAGS"] = "--whatever"
    """}, rules=["env"])
    assert report.ok
    assert codes(report.suppressed) == ["env.import-time-mutation"]


# --------------------------------------------------------------------------
# suppression semantics
# --------------------------------------------------------------------------


def test_allow_matches_specific_code_and_star(tmp_path):
    files = {"mod.py": """
        def f(x):
            # repro: allow[taxonomy.bare-raise] code-level allow
            raise ValueError("a")


        def g(x):
            # repro: allow[*] blanket allow
            raise RuntimeError("b")
    """}
    report = run_on(tmp_path, files, rules=["taxonomy"])
    assert report.ok
    assert len(report.suppressed) == 2


def test_allow_for_other_rule_does_not_suppress(tmp_path):
    report = run_on(tmp_path, {"mod.py": """
        def f(x):
            # repro: allow[span] wrong rule
            raise ValueError("a")
    """}, rules=["taxonomy"])
    assert codes(report.new) == ["taxonomy.bare-raise"]


# --------------------------------------------------------------------------
# baseline mechanism
# --------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    files = {"mod.py": "def f():\n    raise ValueError('grandfathered')\n"}
    root = make_tree(tmp_path, {**REGISTRIES, **files})
    first = run_analysis(root, rules=["taxonomy"])
    assert len(first.new) == 1

    baseline_path = tmp_path / "baseline.json"
    save_baseline(baseline_path, first.new)
    assert load_baseline(baseline_path) == {first.new[0].fingerprint}

    second = run_analysis(root, rules=["taxonomy"],
                          baseline_path=baseline_path)
    assert second.ok
    assert codes(second.baselined) == ["taxonomy.bare-raise"]
    assert not second.new  # zero drift: load -> re-scan -> all baselined


def test_baseline_survives_line_drift_not_content_change(tmp_path):
    files = {"mod.py": "def f():\n    raise ValueError('grandfathered')\n"}
    root = make_tree(tmp_path, {**REGISTRIES, **files})
    first = run_analysis(root, rules=["taxonomy"])
    baseline_path = tmp_path / "baseline.json"
    save_baseline(baseline_path, first.new)

    # unrelated lines move the finding down: fingerprint must hold
    (root / "mod.py").write_text(
        "import os\n\n\ndef f():\n    raise ValueError('grandfathered')\n")
    drifted = run_analysis(root, rules=["taxonomy"],
                           baseline_path=baseline_path)
    assert drifted.ok and len(drifted.baselined) == 1

    # but editing the offending line itself resurfaces the finding
    (root / "mod.py").write_text(
        "def f():\n    raise ValueError('edited message')\n")
    edited = run_analysis(root, rules=["taxonomy"],
                          baseline_path=baseline_path)
    assert not edited.ok


def test_malformed_baseline_is_loud(tmp_path):
    bad = tmp_path / "baseline.json"
    bad.write_text('{"not": "a baseline"}')
    with pytest.raises(ValueError, match="not a repro.analysis baseline"):
        load_baseline(bad)


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == set()


def test_fingerprint_normalizes_whitespace():
    a = Finding(rule="r", code="r.c", path="p.py", line=3, message="m",
                snippet="raise  ValueError('x')")
    b = Finding(rule="r", code="r.c", path="p.py", line=99, message="m",
                snippet="raise ValueError('x')")
    assert a.fingerprint == b.fingerprint  # line + inner spacing irrelevant


# --------------------------------------------------------------------------
# the real tree: the acceptance gate itself
# --------------------------------------------------------------------------


def test_real_repo_has_no_new_findings():
    report = run_analysis(REAL_ROOT, baseline_path=REAL_BASELINE)
    assert report.ok, "\n".join(f.render() for f in report.new)
    # rules 1-4 are clean on HEAD *without* grandfathering: empty baseline
    assert load_baseline(REAL_BASELINE) == set()
    # the three intentional suppressions are labeled in-code, not silent
    assert len(report.suppressed) == 3
    assert {f.path for f in report.suppressed} == {"launch/dryrun.py",
                                                   "obs/trace.py"}


def test_span_rule_accepts_the_structure_hash_span():
    """``plan.key`` (``resolve_plan``'s structure hash) is in the taxonomy,
    and the ``span`` rule passes the real tree that opens it."""
    from repro.obs.trace import SPAN_NAMES

    assert "plan.key" in SPAN_NAMES
    assert 'span("plan.key")' in (REAL_ROOT / "core" / "spgemm.py").read_text()
    report = run_analysis(REAL_ROOT, rules=["span"])
    assert report.ok and not report.new, [f.render() for f in report.new]


def test_real_repo_scans_all_modules():
    report = run_analysis(REAL_ROOT)
    assert report.stats["modules"] > 60
    assert report.stats["parse_errors"] == 0


# --------------------------------------------------------------------------
# CLI (what the CI analysis job runs)
# --------------------------------------------------------------------------


def _run_cli(args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REAL_ROOT.parent)
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_fails_on_seeded_violation(tmp_path):
    root = make_tree(tmp_path, {
        **REGISTRIES,
        "mod.py": "def f():\n    raise ValueError('seeded')\n",
    })
    out_json = tmp_path / "report.json"
    proc = _run_cli(["--root", str(root), "--json", str(out_json),
                     "--baseline", str(tmp_path / "empty.json")])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "taxonomy.bare-raise" in proc.stdout
    payload = json.loads(out_json.read_text())
    assert payload["ok"] is False
    assert payload["counts"]["new"] == 1
    assert payload["new"][0]["code"] == "taxonomy.bare-raise"
    assert payload["new"][0]["path"] == "mod.py"


def test_cli_passes_on_real_repo(tmp_path):
    out_json = tmp_path / "report.json"
    proc = _run_cli(["--json", str(out_json)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(out_json.read_text())
    assert payload["ok"] is True and payload["counts"]["new"] == 0


def test_cli_rules_subset_and_list_rules(tmp_path):
    root = make_tree(tmp_path, {
        **REGISTRIES,
        "mod.py": "def f():\n    raise ValueError('seeded')\n",
    })
    # scoping to another rule must not trip on the taxonomy violation
    proc = _run_cli(["--root", str(root), "--rules", "span",
                     "--baseline", str(tmp_path / "empty.json")])
    assert proc.returncode == 0, proc.stdout + proc.stderr

    proc = _run_cli(["--list-rules"])
    assert proc.returncode == 0
    for rule_id in all_rule_ids():
        assert rule_id in proc.stdout


def test_cli_update_baseline_grandfathers(tmp_path):
    root = make_tree(tmp_path, {
        **REGISTRIES,
        "mod.py": "def f():\n    raise ValueError('seeded')\n",
    })
    baseline = tmp_path / "baseline.json"
    proc = _run_cli(["--root", str(root), "--baseline", str(baseline),
                     "--update-baseline"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert len(load_baseline(baseline)) == 1

    proc = _run_cli(["--root", str(root), "--baseline", str(baseline)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "baselined" in proc.stdout
