"""Static-analysis pass: rule fixtures, suppressions, CLI, ground truth.

Covers the DESIGN.md §13 contracts: each rule catches its seeded
violation and passes the fixed form, suppressions require justification
and rot loudly when stale, the CLI exits 0/1/2, the real package is
clean, and the fork-safety import closure matches runtime ground truth
(every module it lists really imports without jax).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (Project, baseline_payload, default_rules,
                            load_baseline, run_rules)
from repro.analysis.rules import (ALL_RULES, RULES_BY_NAME, AtomicWriteRule,
                                  BareExceptRule, ForkSafetyRule,
                                  Int64OverflowRule, JitHygieneRule,
                                  RngDisciplineRule, ScopedConfigRule)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(REPO, "src", "repro")


def make_project(tmp_path, files):
    """Build a miniature fake `repro` package tree and load it."""
    root = tmp_path / "repro"
    for rel, source in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    return Project.load(str(root), package_name="repro")


def findings_of(rule, project):
    return list(rule.check(project))


# ------------------------------------------------------------------ #
# fork-safety
# ------------------------------------------------------------------ #
def test_fork_safety_catches_transitive_jax(tmp_path):
    # engine -> helpers -> jax, two hops deep: grep-level tools see only
    # the leaf; the rule must walk the graph and name the chain.
    project = make_project(tmp_path, {
        "core/__init__.py": "from .engine import Session\n",
        "core/engine.py": "from .helpers import f\n\nclass Session: pass\n",
        "core/helpers.py": "import jax\n\ndef f(): return jax\n",
        "core/tuner.py": "def tune(): pass\n",
    })
    findings = findings_of(ForkSafetyRule(), project)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "fork-safety"
    assert f.path == "repro/core/helpers.py"
    assert "repro.core.engine -> repro.core.helpers" in f.message


def test_fork_safety_lazy_import_is_legal(tmp_path):
    # a function-scope import runs post-fork inside the worker: legal.
    project = make_project(tmp_path, {
        "core/engine.py": "def go():\n    import jax\n    return jax\n",
        "core/tuner.py": "def tune(): pass\n",
    })
    assert findings_of(ForkSafetyRule(), project) == []


def test_fork_safety_type_checking_import_is_legal(tmp_path):
    project = make_project(tmp_path, {
        "core/engine.py": (
            "from typing import TYPE_CHECKING\n"
            "if TYPE_CHECKING:\n    import jax\n"),
        "core/tuner.py": "def tune(): pass\n",
    })
    assert findings_of(ForkSafetyRule(), project) == []


def test_fork_safety_unreachable_jax_is_legal(tmp_path):
    # jax at module scope OUTSIDE the worker closure must not flag.
    project = make_project(tmp_path, {
        "core/engine.py": "x = 1\n",
        "core/tuner.py": "y = 2\n",
        "kernels/ops.py": "import jax\n",
    })
    assert findings_of(ForkSafetyRule(), project) == []


def test_fork_safety_closure_matches_runtime_ground_truth(tmp_path):
    """Every module the rule says is fork-worker-reachable must import
    cleanly with jax stubbed to raise — i.e. the static closure is sound
    against what the interpreter actually does."""
    project = Project.load(PKG_DIR)
    closure = ForkSafetyRule().reachable(project)
    assert "repro.core.engine" in closure
    assert "repro.core.tuner" in closure

    stub_dir = tmp_path / "stubs"
    stub_dir.mkdir()
    (stub_dir / "jax.py").write_text(
        "raise ImportError('jax imported in fork-worker closure')\n")
    (stub_dir / "jaxlib.py").write_text(
        "raise ImportError('jaxlib imported in fork-worker closure')\n")

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(stub_dir), os.path.join(REPO, "src")])
    script = (
        "import importlib, json, sys\n"
        "for name in json.loads(sys.argv[1]):\n"
        "    importlib.import_module(name)\n"
        "repro_mods = sorted(m for m in sys.modules"
        " if m.startswith('repro'))\n"
        "print(json.dumps(repro_mods))\n")
    proc = subprocess.run(
        [sys.executable, "-c", script, json.dumps(sorted(closure))],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr

    # soundness the other way: nothing got pulled in at import time that
    # the static graph missed
    imported = set(json.loads(proc.stdout))
    assert imported <= set(closure) | {"repro"}


# ------------------------------------------------------------------ #
# int64-overflow
# ------------------------------------------------------------------ #
INT64_BAD = """\
    import numpy as np

    def traffic(events, tile_bytes):
        acc = np.zeros(4)
        acc += events * tile_bytes
        return acc
"""

INT64_GOOD = """\
    import numpy as np

    def traffic(events, tile_bytes):
        acc = np.zeros(4)
        acc += events.astype(np.float64) * tile_bytes
        return acc
"""


def test_int64_overflow_catches_raw_product(tmp_path):
    project = make_project(tmp_path, {"perf.py": INT64_BAD})
    findings = findings_of(Int64OverflowRule(), project)
    assert len(findings) == 1
    assert findings[0].rule == "int64-overflow"
    assert ".astype(np.float64)" in findings[0].message


def test_int64_overflow_promoted_product_is_legal(tmp_path):
    project = make_project(tmp_path, {"perf.py": INT64_GOOD})
    assert findings_of(Int64OverflowRule(), project) == []


def test_int64_overflow_pure_python_function_is_exempt(tmp_path):
    # Python ints are arbitrary precision; only numpy-touching code wraps.
    project = make_project(tmp_path, {"perf.py": """\
        import numpy as np

        def scalar_bytes(event_count, tile_bytes):
            return event_count * tile_bytes
    """})
    assert findings_of(Int64OverflowRule(), project) == []


# ------------------------------------------------------------------ #
# jit-hygiene
# ------------------------------------------------------------------ #
JIT_GLOBAL_BAD = """\
    import jax

    _INTERPRET = False

    def set_interpret(v):
        global _INTERPRET
        _INTERPRET = v

    @jax.jit
    def kernel(x):
        if _INTERPRET:
            return x
        return x + 1
"""

JIT_CONFIG_BAD = """\
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("n",))
    def kernel(x, config, n):
        return x
"""

JIT_CONFIG_GOOD = """\
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("config", "n"))
    def kernel(x, config, n):
        return x
"""


def test_jit_hygiene_catches_mutable_global_read(tmp_path):
    project = make_project(tmp_path, {"ops.py": JIT_GLOBAL_BAD})
    findings = findings_of(JitHygieneRule(), project)
    assert len(findings) == 1
    assert "_INTERPRET" in findings[0].message


def test_jit_hygiene_catches_traced_config_param(tmp_path):
    project = make_project(tmp_path, {"ops.py": JIT_CONFIG_BAD})
    findings = findings_of(JitHygieneRule(), project)
    assert len(findings) == 1
    assert "'config'" in findings[0].message


def test_jit_hygiene_static_config_is_legal(tmp_path):
    project = make_project(tmp_path, {"ops.py": JIT_CONFIG_GOOD})
    assert findings_of(JitHygieneRule(), project) == []


def test_jit_hygiene_ignores_jax_free_modules(tmp_path):
    # `jit` from another library (e.g. numba) is out of scope
    project = make_project(tmp_path, {"ops.py": """\
        from numba import jit

        @jit
        def kernel(x, config):
            return x
    """})
    assert findings_of(JitHygieneRule(), project) == []


# ------------------------------------------------------------------ #
# scoped-config
# ------------------------------------------------------------------ #
def test_scoped_config_catches_global_update(tmp_path):
    project = make_project(tmp_path, {"model.py": """\
        import jax

        jax.config.update("jax_enable_x64", True)
    """})
    findings = findings_of(ScopedConfigRule(), project)
    assert len(findings) == 1
    assert "jax.config.update" in findings[0].message


def test_scoped_config_with_enable_x64_is_legal(tmp_path):
    project = make_project(tmp_path, {"model.py": """\
        import jax
        from jax import enable_x64

        def fit():
            with enable_x64(True):
                return 1

        def fit_qualified():
            with jax.enable_x64(True):
                return 1
    """})
    assert findings_of(ScopedConfigRule(), project) == []


def test_scoped_config_catches_unscoped_enable_x64_call(tmp_path):
    project = make_project(tmp_path, {"model.py": """\
        import jax
        from jax import enable_x64

        def fit():
            ctx = enable_x64(True)
            ctx.__enter__()
            return 1

        def fit_qualified():
            ctx = jax.enable_x64(True)
            ctx.__enter__()
            return 1
    """})
    findings = findings_of(ScopedConfigRule(), project)
    assert len(findings) == 2
    assert all("outside a `with`" in f.message for f in findings)


# ------------------------------------------------------------------ #
# rng-discipline
# ------------------------------------------------------------------ #
def test_rng_discipline_catches_global_stream(tmp_path):
    project = make_project(tmp_path, {"sample.py": """\
        import random

        def pick(xs):
            return random.choice(xs)
    """})
    findings = findings_of(RngDisciplineRule(), project)
    assert len(findings) == 1
    assert "process-global stream" in findings[0].message


def test_rng_discipline_catches_from_import_and_legacy_numpy(tmp_path):
    project = make_project(tmp_path, {"sample.py": """\
        import numpy as np
        from random import randint

        def noise(n):
            return np.random.rand(n)
    """})
    rules = {f.rule for f in findings_of(RngDisciplineRule(), project)}
    msgs = [f.message for f in findings_of(RngDisciplineRule(), project)]
    assert rules == {"rng-discipline"}
    assert len(msgs) == 2


def test_rng_discipline_seeded_instances_are_legal(tmp_path):
    project = make_project(tmp_path, {"sample.py": """\
        import random
        import numpy as np
        import jax

        def pick(xs, seed, key):
            rng = random.Random(seed)
            g = np.random.default_rng(seed)
            u = jax.random.uniform(key)
            return rng.choice(xs), g.integers(10), u
    """})
    assert findings_of(RngDisciplineRule(), project) == []


# ------------------------------------------------------------------ #
# atomic-write
# ------------------------------------------------------------------ #
def test_atomic_write_catches_bare_open_in_registry(tmp_path):
    project = make_project(tmp_path, {"registry/store.py": """\
        def save(path, data):
            with open(path, "w") as f:
                f.write(data)
    """})
    findings = findings_of(AtomicWriteRule(), project)
    assert len(findings) == 1
    assert "os.replace" in findings[0].message


def test_atomic_write_mkstemp_replace_is_legal(tmp_path):
    project = make_project(tmp_path, {"registry/store.py": """\
        import os
        import tempfile

        def save(path, data):
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))
            with os.fdopen(fd, "w") as f:
                f.write(data)
            os.replace(tmp, path)
    """})
    assert findings_of(AtomicWriteRule(), project) == []


def test_atomic_write_o_append_is_legal_but_truncate_is_not(tmp_path):
    project = make_project(tmp_path, {"obs/trace.py": """\
        import os

        def opener_ok(path):
            return os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT)

        def opener_bad(path):
            return os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC)
    """})
    findings = findings_of(AtomicWriteRule(), project)
    assert len(findings) == 1
    assert "O_APPEND" in findings[0].message


def test_atomic_write_out_of_scope_package_is_exempt(tmp_path):
    project = make_project(tmp_path, {"launch/serve.py": """\
        def save(path, data):
            with open(path, "w") as f:
                f.write(data)
    """})
    assert findings_of(AtomicWriteRule(), project) == []


# ------------------------------------------------------------------ #
# suppressions + baselines (the runner)
# ------------------------------------------------------------------ #
RNG_BAD_LINE = "    return random.choice(xs)"


def runner_project(tmp_path, tail):
    return make_project(tmp_path, {"sample.py": (
        "import random\n\ndef pick(xs):\n" + tail + "\n")})


def test_justified_suppression_suppresses(tmp_path):
    project = runner_project(
        tmp_path,
        RNG_BAD_LINE + "  # repro: ignore[rng-discipline] -- test fixture")
    report = run_rules(project, [RngDisciplineRule()])
    assert report.exit_code == 0
    [f] = report.findings
    assert f.suppressed and f.justification == "test fixture"


def test_unjustified_suppression_fails_gate(tmp_path):
    project = runner_project(
        tmp_path, RNG_BAD_LINE + "  # repro: ignore[rng-discipline]")
    report = run_rules(project, [RngDisciplineRule()])
    assert report.exit_code == 1
    rules = sorted(f.rule for f in report.blocking)
    assert rules == ["rng-discipline", "suppression-missing-justification"]


def test_stale_suppression_fails_gate(tmp_path):
    project = runner_project(
        tmp_path,
        "    return xs[0]  # repro: ignore[rng-discipline] -- was needed")
    report = run_rules(project, [RngDisciplineRule()])
    assert [f.rule for f in report.blocking] == ["stale-suppression"]


def test_unknown_suppressed_rule_fails_gate(tmp_path):
    project = runner_project(
        tmp_path, "    return xs[0]  # repro: ignore[no-such-rule] -- x")
    report = run_rules(project, [RngDisciplineRule()],
                       all_rule_names=list(RULES_BY_NAME))
    assert [f.rule for f in report.blocking] == ["unknown-suppressed-rule"]


def test_partial_run_leaves_other_rules_suppressions_alone(tmp_path):
    # an atomic-write suppression must not read as stale when only the
    # rng rule is selected
    project = make_project(tmp_path, {"registry/store.py": """\
        def save(path, data):
            with open(path, "w") as f:  # repro: ignore[atomic-write] -- x
                f.write(data)
    """})
    report = run_rules(project, [RngDisciplineRule()],
                       all_rule_names=list(RULES_BY_NAME))
    assert report.findings == []


def test_baseline_accepts_without_blocking(tmp_path):
    project = runner_project(tmp_path, RNG_BAD_LINE)
    first = run_rules(project, [RngDisciplineRule()])
    assert first.exit_code == 1

    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(baseline_payload(first.findings)))
    second = run_rules(project, [RngDisciplineRule()],
                       baseline=load_baseline(str(path)))
    assert second.exit_code == 0
    assert [f.baselined for f in second.findings] == [True]


# ------------------------------------------------------------------ #
# CLI + the real package
# ------------------------------------------------------------------ #
def run_cli(*argv, cwd=REPO):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_clean_on_real_package_and_writes_json(tmp_path):
    out = tmp_path / "report.json"
    proc = run_cli("--json", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(out.read_text())
    assert payload["summary"]["blocking"] == 0
    assert set(payload["rules"]) == set(RULES_BY_NAME)
    assert payload["modules_scanned"] > 50


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "repro"
    bad.mkdir()
    (bad / "sample.py").write_text(
        "import random\n\ndef pick(xs):\n    return random.choice(xs)\n")
    assert run_cli("--root", str(bad)).returncode == 1
    assert run_cli("--rule", "no-such-rule").returncode == 2
    assert run_cli("--root", str(tmp_path / "missing")).returncode == 2
    assert run_cli("--list-rules").returncode == 0


def test_mypy_baseline_clean():
    """The checked-in mypy baseline holds over core + registry.

    mypy is not baked into the runtime image; locally this skips, in CI
    (which installs mypy) it blocks.
    """
    pytest.importorskip("mypy")
    proc = subprocess.run(
        [sys.executable, "-m", "mypy", "--config-file", "mypy.ini"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_every_rule_has_name_description_and_fixture():
    names = [cls.name for cls in ALL_RULES]
    assert len(names) == len(set(names)) >= 7
    for cls in ALL_RULES:
        assert cls.name and cls.description


# ------------------------------------------------------------------ #
# bare-except
# ------------------------------------------------------------------ #
def _bare_except_findings(tmp_path, body):
    project = make_project(tmp_path, {"svc.py": body})
    return findings_of(BareExceptRule(), project)


def test_bare_except_catches_silent_swallow(tmp_path):
    findings = _bare_except_findings(tmp_path, """\
        def drain():
            try:
                work()
            except Exception:
                pass
    """)
    assert len(findings) == 1
    assert findings[0].rule == "bare-except"
    assert findings[0].line == 4


def test_bare_except_catches_bare_and_tuple_forms(tmp_path):
    findings = _bare_except_findings(tmp_path, """\
        def a():
            try:
                work()
            except:
                stats = stats + 1
        def b():
            try:
                work()
            except (ValueError, Exception):
                counters["x"] = 1
    """)
    assert len(findings) == 2


def test_bare_except_counter_bump_alone_is_still_silent(tmp_path):
    # the original TuningService._drain bug: a mute stats counter is not
    # reporting — nothing human-visible records *what* failed
    findings = _bare_except_findings(tmp_path, """\
        def drain():
            try:
                work()
            except Exception:
                stats["tune_errors"] += 1
    """)
    assert len(findings) == 1


def test_bare_except_legal_forms_pass(tmp_path):
    findings = _bare_except_findings(tmp_path, """\
        import logging
        _log = logging.getLogger(__name__)

        def reraises():
            try:
                work()
            except Exception:
                cleanup()
                raise

        def logs():
            try:
                work()
            except Exception:
                _log.warning("work failed")

        def uses_bound():
            try:
                work()
            except Exception as exc:
                record(repr(exc))

        def narrow_is_policy():
            try:
                work()
            except OSError:
                pass
    """)
    assert findings == []


def test_bare_except_suppression_needs_justification(tmp_path):
    project = make_project(tmp_path, {"svc.py": """\
        def drain():
            try:
                work()
            except Exception:  # repro: ignore[bare-except] -- probe only; failure means the backend is absent, the caller falls back
                pass
    """})
    report = run_rules(project, [BareExceptRule()])
    assert [f for f in report.findings if f.blocking] == []
    assert any(f.suppressed for f in report.findings)


def test_bare_except_real_tree_is_clean():
    project = Project.load(PKG_DIR, package_name="repro")
    report = run_rules(project, [BareExceptRule()],
                       all_rule_names=list(RULES_BY_NAME))
    assert [f.render() for f in report.findings if f.blocking] == []
