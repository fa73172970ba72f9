"""Host-only tests for the static invariant linter (parmmg_tpu/lint).

No jax import anywhere in this module — the linter's contract is that
it runs jax-free in seconds, and these tests inherit that (near-zero
tier-1 budget cost).  Each rule gets a known-clean + known-dirty
fixture pair; the engine gets suppression-grammar and baseline-gate
coverage; and the real tree is gated in-process exactly as
``run_tests.sh --lint`` does.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from parmmg_tpu import lint                                    # noqa: E402
from parmmg_tpu.lint import SourceFile, gate, load_baseline    # noqa: E402


def lint_sources(srcs: dict, rules, readme_text: str = ""):
    """Run a rule subset over literal {relpath: source} fixtures."""
    files = {rel: SourceFile(rel, txt) for rel, txt in srcs.items()}
    return lint.run_lint(rules=rules, files=files,
                         readme_text=readme_text)


def keys(report):
    return sorted(v.key for v in report.violations)


# ---------------------------------------------------------------------------
# R1 jit-hygiene
# ---------------------------------------------------------------------------
R1_CLEAN = '''
import jax
from functools import lru_cache, partial

analyze = jax.jit(lambda x: x)                    # module assignment

@partial(jax.jit, static_argnames=("n",))         # module decorator
def stepper(x, n):
    return x

_CACHE = {}

def builder(key):                                 # CAPS cache store
    if key in _CACHE:
        return _CACHE[key]
    @jax.jit
    def run(x):
        return x
    _CACHE[key] = run
    return run

@lru_cache(maxsize=None)                          # lru_cache builder
def cached_builder(n):
    return jax.jit(lambda x: x + n)

def governed_builder(spec):
    from parmmg_tpu.utils.compilecache import governed
    return governed("x.y", budget=2)(jax.jit(lambda x: x))

def _make():
    return jax.jit(lambda x: x)

made_once = _make()                               # built at module level

class Steps:
    def __init__(self):
        self.fn = jax.jit(lambda x: x)            # instance cache
'''

R1_DIRTY = '''
import jax

def hot_loop(x):
    fn = jax.jit(lambda a: a + 1)                 # fresh jit per call
    return fn(x)
'''


def test_r1_accepts_every_cache_idiom():
    rep = lint_sources({"parmmg_tpu/ops/clean.py": R1_CLEAN}, ["R1"])
    assert keys(rep) == []


def test_r1_flags_per_call_jit():
    rep = lint_sources({"parmmg_tpu/ops/dirty.py": R1_DIRTY}, ["R1"])
    assert len(rep.violations) == 1
    v = rep.violations[0]
    assert v.rule == "R1" and v.scope == "hot_loop"
    assert v.detail == "jax.jit"


def test_r1_flags_shard_map_alias():
    src = ("from parmmg_tpu.utils.jaxcompat import shard_map\n"
           "def f(mesh):\n"
           "    return shard_map(lambda x: x, mesh=mesh,\n"
           "                     in_specs=None, out_specs=None)\n")
    rep = lint_sources({"parmmg_tpu/parallel/x.py": src}, ["R1"])
    assert [v.detail for v in rep.violations] == ["shard_map"]


def test_r1_module_level_decorator_not_flagged():
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x\n")
    rep = lint_sources({"parmmg_tpu/ops/x.py": src}, ["R1"])
    assert keys(rep) == []


# ---------------------------------------------------------------------------
# R2 host-sync reachability
# ---------------------------------------------------------------------------
R2_DIRTY = '''
import numpy as np

def grouped_adapt_pass(state):                    # root
    return helper(state)

def helper(state):                                # reachable
    return np.asarray(state)

def cold_path(state):                             # NOT reachable
    return np.asarray(state)
'''


def test_r2_reachability_flags_hot_not_cold():
    rep = lint_sources({"parmmg_tpu/parallel/x.py": R2_DIRTY}, ["R2"])
    scopes = sorted(v.scope for v in rep.violations)
    assert scopes == ["grouped_adapt_pass", "helper"] or \
        scopes == ["helper"]
    assert all(v.detail == "np.asarray" for v in rep.violations)
    assert not any(v.scope == "cold_path" for v in rep.violations)


def test_r2_def_line_suppression_exempts_function():
    src = ('import numpy as np\n'
           'def grouped_adapt_pass(s):\n'
           '    return fallback(s)\n'
           '# lint: ok(R2) — documented KS-overflow host fallback\n'
           'def fallback(s):\n'
           '    return np.asarray(s)\n')
    rep = lint_sources({"parmmg_tpu/parallel/x.py": src}, ["R2"])
    assert keys(rep) == []
    # the def-line exemption is a recorded suppression, not a silent
    # drop — the audit listing must show the (violation, reason) pair
    assert len(rep.suppressed) == 1
    v, s = rep.suppressed[0]
    assert v.rule == "R2" and "fallback" in s.reason


def test_r2_env_read_cast_not_flagged():
    src = ('import os\n'
           'def grouped_adapt_pass(s):\n'
           '    return float(os.environ.get("X", "0"))\n')
    rep = lint_sources({"parmmg_tpu/parallel/x.py": src}, ["R2"])
    assert keys(rep) == []


def test_r2_def_suppression_on_decorated_function():
    src = ('import functools\n'
           'import numpy as np\n'
           'def grouped_adapt_pass(s):\n'
           '    return fallback(s)\n'
           '# lint: ok(R2) — documented host fallback (decorated)\n'
           '@functools.wraps(print)\n'
           'def fallback(s):\n'
           '    return np.asarray(s)\n')
    rep = lint_sources({"parmmg_tpu/parallel/x.py": src}, ["R2"])
    assert keys(rep) == [] and len(rep.suppressed) == 1


def test_r1_governed_does_not_exempt_sibling_jit():
    # a governed program in the function must NOT blanket-exempt a
    # second, per-call bare jit built in the same function
    src = ('import jax\n'
           'from parmmg_tpu.utils.compilecache import governed\n'
           'def builder():\n'
           '    good = governed("x.y", budget=1)(jax.jit(lambda x: x))\n'
           '    bad = jax.jit(lambda y: y + 1)\n'
           '    return good, bad\n')
    rep = lint_sources({"parmmg_tpu/ops/x.py": src}, ["R1"])
    assert len(rep.violations) == 1
    assert rep.violations[0].line == 5


def test_r1_shard_map_wrapper_ok_when_builder_governs():
    # the dist_adapt_block idiom: bare shard_map wrap, jit governed in
    # a later statement of the same builder
    src = ('import jax\n'
           'from parmmg_tpu.utils.jaxcompat import shard_map\n'
           'from parmmg_tpu.utils.compilecache import governed\n'
           'def builder(dmesh, spec):\n'
           '    fn = shard_map(lambda x: x, mesh=dmesh,\n'
           '                   in_specs=spec, out_specs=spec)\n'
           '    return governed("d.block")(jax.jit(fn))\n')
    rep = lint_sources({"parmmg_tpu/parallel/x.py": src}, ["R1"])
    assert keys(rep) == []


# ---------------------------------------------------------------------------
# R3 obs-routing
# ---------------------------------------------------------------------------
def test_r3_flags_print_outside_obs_only():
    srcs = {
        "parmmg_tpu/ops/a.py": "def f():\n    print('x')\n",
        "parmmg_tpu/obs/b.py": "def g():\n    print('x')\n",
        "scripts/c.py": "print('artifact')\n",
    }
    rep = lint_sources(srcs, ["R3"])
    assert [v.path for v in rep.violations] == ["parmmg_tpu/ops/a.py"]


def test_r3_suppression_with_reason_is_honoured():
    src = "def f():\n    print('x')  # lint: ok(R3) — stdout contract\n"
    rep = lint_sources({"parmmg_tpu/ops/a.py": src}, ["R3"])
    assert keys(rep) == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R4 knob registry
# ---------------------------------------------------------------------------
KNOBS_FIXTURE = '''
class Knob:
    def __init__(self, type, default, doc): pass

KNOBS = {
    "PARMMG_GOOD": Knob("int", "1", "a used knob"),
    "PARMMG_DEAD": Knob("int", "0", "nothing reads this"),
}
'''

R4_READS = '''
import os
a = os.environ.get("PARMMG_GOOD", "1")
b = os.environ.get("PARMMG_ROGUE", "")
'''


def test_r4_unregistered_read_dead_knob_and_readme_drift():
    rep = lint_sources(
        {"parmmg_tpu/api/knobs.py": KNOBS_FIXTURE,
         "parmmg_tpu/ops/x.py": R4_READS},
        ["R4"], readme_text="only PARMMG_GOOD and PARMMG_GHOST here")
    det = sorted((v.detail, v.path) for v in rep.violations)
    # rogue read, dead knob, dead knob missing from README, ghost in README
    assert ("PARMMG_ROGUE", "parmmg_tpu/ops/x.py") in det
    assert ("PARMMG_DEAD", "parmmg_tpu/api/knobs.py") in det
    assert ("PARMMG_GHOST", "README.md") in det
    msgs = [v.message for v in rep.violations
            if v.detail == "PARMMG_DEAD"]
    assert any("no usage" in m for m in msgs)
    assert any("missing from README" in m for m in msgs)


def test_r4_clean_when_registry_readme_and_reads_agree():
    rep = lint_sources(
        {"parmmg_tpu/api/knobs.py": KNOBS_FIXTURE.replace(
            '    "PARMMG_DEAD": Knob("int", "0", "nothing reads this"),\n',
            ""),
         "parmmg_tpu/ops/x.py":
             'import os\nv = os.environ.get("PARMMG_GOOD", "1")\n'},
        ["R4"], readme_text="`PARMMG_GOOD` does things")
    assert keys(rep) == []


def test_r4_helper_env_reader_is_scanned():
    src = ('def _env_int(name, d):\n'
           '    import os\n'
           '    return int(os.environ.get(name, str(d)) or d)\n'
           'v = _env_int("PARMMG_NOT_DECLARED", 4)\n')
    rep = lint_sources(
        {"parmmg_tpu/api/knobs.py": KNOBS_FIXTURE,
         "parmmg_tpu/serve/x.py": src},
        ["R4"], readme_text="PARMMG_GOOD PARMMG_DEAD")
    assert any(v.detail == "PARMMG_NOT_DECLARED"
               for v in rep.violations)


# ---------------------------------------------------------------------------
# R5 jaxcompat
# ---------------------------------------------------------------------------
def test_r5_flags_direct_shim_spellings():
    srcs = {
        "parmmg_tpu/parallel/bad1.py":
            "from jax.experimental.shard_map import shard_map\n",
        "parmmg_tpu/parallel/bad2.py":
            "import jax\nn = jax.lax.axis_size('shard')\n",
        "parmmg_tpu/utils/jaxcompat.py":
            "from jax.experimental.shard_map import shard_map\n",
    }
    rep = lint_sources(srcs, ["R5"])
    paths = sorted(v.path for v in rep.violations)
    assert paths == ["parmmg_tpu/parallel/bad1.py",
                     "parmmg_tpu/parallel/bad2.py"]


def test_r5_flags_plain_module_import():
    src = "import jax.experimental.shard_map as sm\n"
    rep = lint_sources({"parmmg_tpu/parallel/bad3.py": src}, ["R5"])
    assert [v.detail for v in rep.violations] == \
        ["jax.experimental.shard_map"]


def test_r5_shim_import_is_clean():
    src = "from parmmg_tpu.utils.jaxcompat import shard_map, axis_size\n"
    rep = lint_sources({"parmmg_tpu/parallel/ok.py": src}, ["R5"])
    assert keys(rep) == []


# ---------------------------------------------------------------------------
# R6 name schemes
# ---------------------------------------------------------------------------
FAULTS_FIXTURE = 'SITES = {"dispatch.chunk": "xla", "halo.exchange": "xla"}\n'
RECOVER_FIXTURE = 'LADDER = ("retry", "halo_dense", "lowfailure")\n'


def _r6(src):
    return lint_sources(
        {"parmmg_tpu/resilience/faults.py": FAULTS_FIXTURE,
         "parmmg_tpu/resilience/recover.py": RECOVER_FIXTURE,
         "parmmg_tpu/serve/x.py": src}, ["R6"])


def test_r6_dynamic_and_malformed_names():
    rep = _r6('from parmmg_tpu.obs.metrics import REGISTRY\n'
              'def f(k):\n'
              '    REGISTRY.counter(f"serve.{k}").inc()\n'
              '    REGISTRY.gauge("Serve.BadCase").set(1)\n'
              '    REGISTRY.counter("serve.ok").inc()\n')
    det = sorted(v.detail for v in rep.violations)
    assert det == ["metric.counter:dynamic",
                   "metric.gauge:Serve.BadCase"]


def test_r6_ifexp_over_literals_is_static():
    rep = _r6('from parmmg_tpu.obs.metrics import REGISTRY\n'
              'def f(ok):\n'
              '    REGISTRY.counter("a.ok" if ok else "a.bad").inc()\n')
    assert keys(rep) == []


def test_r6_faultpoint_site_must_be_registered():
    rep = _r6('from parmmg_tpu.resilience.faults import faultpoint\n'
              'def f():\n'
              '    faultpoint("halo.exchange")\n'
              '    faultpoint("made.up_site")\n')
    assert [v.detail for v in rep.violations] == \
        ["faultpoint:made.up_site"]


def test_r6_ladder_step_must_be_registered():
    rep = _r6('from parmmg_tpu.resilience.recover import ladder_step\n'
              'def f():\n'
              '    ladder_step("halo_dense", site="halo.exchange")\n'
              '    ladder_step("wishful_step")\n')
    assert [v.detail for v in rep.violations] == \
        ["ladder_step:wishful_step"]


# ---------------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------------
def test_suppression_without_reason_fails():
    # concatenation keeps this *invalid* example from matching when
    # the real-tree scan reads this test file's own source
    src = "def f():\n    print('x')  # lint: " + "ok(R3)\n"
    rep = lint_sources({"parmmg_tpu/ops/a.py": src}, ["R3"])
    # the print is NOT suppressed and the bad suppression is reported
    assert len(rep.violations) == 1
    assert len(rep.bad) == 1 and rep.bad[0].rule == "SUPP"
    res = gate(rep, {})
    assert not res.ok


def test_suppression_unknown_rule_fails():
    src = "x = 1  # lint: " + "ok(R99) — sounds official\n"
    rep = lint_sources({"parmmg_tpu/ops/a.py": src}, ["R3"])
    assert len(rep.bad) == 1
    assert "unknown rule" in rep.bad[0].message


def test_standalone_suppression_skips_continuation_comments():
    src = ("def f():\n"
           "    # lint: ok(R3) — a reason that wraps onto the\n"
           "    # next comment line before the code\n"
           "    print('x')\n")
    rep = lint_sources({"parmmg_tpu/ops/a.py": src}, ["R3"])
    assert keys(rep) == [] and len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# baseline gate semantics
# ---------------------------------------------------------------------------
def test_baseline_count_pinning_and_retirement():
    two = "def f():\n    print('a')\n    print('b')\n"
    rep = lint_sources({"parmmg_tpu/ops/a.py": two}, ["R3"])
    key = rep.violations[0].key
    assert all(v.key == key for v in rep.violations)

    # exact count: clean
    assert gate(rep, {key: 2}).ok
    # count above ceiling: the excess is new
    res = gate(rep, {key: 1})
    assert not res.ok and len(res.new) == 1
    # unknown key in the baseline shows as retired (burn-down)
    res = gate(rep, {key: 2, "R3:parmmg_tpu/ops/gone.py:f:print": 3})
    assert res.ok and res.burndown["R3"]["retired"] == 3


def test_baseline_never_applies_to_r4():
    rep = lint_sources(
        {"parmmg_tpu/api/knobs.py": KNOBS_FIXTURE,
         "parmmg_tpu/ops/x.py":
             'import os\nv = os.environ.get("PARMMG_ROGUE", "")\n'},
        ["R4"], readme_text="PARMMG_GOOD PARMMG_DEAD mentioned")
    rogue = [v for v in rep.violations if v.detail == "PARMMG_ROGUE"]
    assert rogue
    res = gate(rep, {rogue[0].key: 99})      # grandfathering ignored
    assert any(v.detail == "PARMMG_ROGUE" for v in res.new)


def test_baseline_payload_roundtrip(tmp_path):
    rep = lint_sources(
        {"parmmg_tpu/ops/a.py": "def f():\n    print('x')\n"}, ["R3"])
    payload = lint.baseline_payload(rep)
    p = tmp_path / "lint_baseline.json"
    p.write_text(json.dumps(payload))
    loaded = load_baseline(str(p))
    assert gate(rep, loaded).ok


# ---------------------------------------------------------------------------
# the real tree (the tier-1 inclusion of the gate)
# ---------------------------------------------------------------------------
def test_repo_tree_is_lint_clean():
    report = lint.run_lint(ROOT)
    result = gate(report, load_baseline(
        os.path.join(ROOT, "lint_baseline.json")))
    assert result.ok, lint.format_report(report, result)
    # every suppression in the tree carries a reason by construction;
    # R4 must be exactly clean (no baseline key can hide it)
    assert not any(k.startswith("R4:") for k in load_baseline(
        os.path.join(ROOT, "lint_baseline.json")))


def test_knob_registry_matches_readme_table():
    # the README table is generated from the registry; regenerating it
    # in-process must cover every registered knob name
    from parmmg_tpu.api import knobs
    table = knobs.knob_table_md()
    for name in knobs.registered():
        assert f"`{name}`" in table
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    for name in knobs.registered():
        assert name in readme


def test_knobs_get_rejects_undeclared():
    from parmmg_tpu.api import knobs
    with pytest.raises(KeyError):
        knobs.get("PARMMG_NOT_A_KNOB")
    assert knobs.get("PARMMG_TRACE_RING") in ("4096",) or \
        knobs.get("PARMMG_TRACE_RING") == os.environ.get(
            "PARMMG_TRACE_RING")


def test_unknown_rule_id_is_a_usage_error():
    with pytest.raises(ValueError, match="unknown lint rule"):
        lint.run_lint(rules=("R99",), files={})
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lint_check.py"),
         "--rules", "R99"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 2
    assert "unknown lint rule" in r.stderr


def test_lint_cli_runs_jaxfree_and_green():
    # subprocess: verifies the gate end-to-end INCLUDING the linter's
    # own "never imported jax" self-check (rc 2 if it ever does)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lint_check.py")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint: OK" in r.stdout


def test_linter_itself_imports_no_jax():
    # in-process guard: importing the lint package must not drag jax in
    # (only meaningful when jax is not already loaded by earlier tests)
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "import parmmg_tpu.lint; "
         "sys.exit(1 if 'jax' in sys.modules else 0)" % ROOT],
        capture_output=True, timeout=60)
    assert r.returncode == 0
