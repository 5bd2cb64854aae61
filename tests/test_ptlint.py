"""ptlint (paddle_tpu.analysis) — per-rule fixtures (true positive,
true negative, suppression, baseline round-trip) and the repo self-lint
gate: the shipped tree must carry ZERO non-baselined findings.

Everything here is pure-AST (no tracing, no device), so the whole file
stays tier-1 fast.
"""

import os
import subprocess
import sys

import pytest

from paddle_tpu.analysis import baseline, default_rules, load_project, run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a minimal flags.py so PT005 has a contract registry in fixture trees
FLAGS_SRC = """
def declare_env(name, help="", default=None, owner=""):
    pass

def declare_env_prefix(prefix, help="", owner=""):
    pass

declare_env("PT_DECLARED_KNOB", "a declared knob")
declare_env_prefix("PT_FLAGS_", "flag overrides")
"""


def _lint(tmp_path, sources, rules=None):
    d = tmp_path / "pkg"
    d.mkdir(exist_ok=True)
    for name, src in sources.items():
        p = d / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    project = load_project([str(d)], root=str(tmp_path))
    return run(project, rules)


def _rules_hit(findings):
    return {f.rule for f in findings}


# -- PT001: host syncs -------------------------------------------------------

def test_pt001_item_in_jit_positive(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax
import jax.numpy as jnp

def _step(x):
    y = jnp.sum(x)
    return y.item()

step = jax.jit(_step)
"""})
    assert any(f.rule == "PT001" and ".item()" in f.message
               for f in findings)


def test_pt001_scope_negative(tmp_path):
    """The same .item() OUTSIDE any traced/dispatch scope is fine."""
    findings = _lint(tmp_path, {"mod.py": """
import jax.numpy as jnp

def host_summary(x):
    return jnp.sum(x).item()
"""})
    # host_summary is never jitted nor reachable from a dispatch root:
    # .item() there is ordinary host code
    assert "PT001" not in _rules_hit(findings)


def test_pt001_reaches_through_calls(tmp_path):
    """Scope is transitive: a helper CALLED from a jitted function is
    traced code too."""
    findings = _lint(tmp_path, {"mod.py": """
import jax
import numpy as np
import jax.numpy as jnp

def helper(x):
    return float(jnp.max(x))

def _step(x):
    return helper(x)

step = jax.jit(_step)
"""})
    hits = [f for f in findings if f.rule == "PT001"]
    assert hits and "helper" in hits[0].symbol


def test_pt001_metadata_copy_anywhere(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import numpy as np

def plan(x):
    return np.asarray(x).shape[:2]
"""})
    hits = [f for f in findings if f.rule == "PT001"]
    assert hits and "metadata" in hits[0].message


def test_pt001_suppression(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax
import jax.numpy as jnp

def _step(x):
    y = jnp.sum(x)
    # ptlint: disable=PT001 -- deliberate, documented
    return y.item()

step = jax.jit(_step)
"""})
    assert "PT001" not in _rules_hit(findings)


# -- PT002: retrace hazards --------------------------------------------------

def test_pt002_jit_in_loop_positive(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax

def train(fns, xs):
    out = []
    for x in xs:
        out.append(jax.jit(lambda v: v + 1)(x))
    return out
"""})
    assert any(f.rule == "PT002" and "loop" in f.message
               for f in findings)


def test_pt002_builder_negative(tmp_path):
    """jit in a build-once function (no loop) is the idiom, not a
    hazard."""
    findings = _lint(tmp_path, {"mod.py": """
import jax

def build_step(fn):
    def step(params, batch):
        return fn(params, batch)
    return jax.jit(step)
"""})
    assert "PT002" not in _rules_hit(findings)


def test_pt002_mutated_global_closure(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax

SCALE = 1.0
BIAS = 0.0

def set_scale(v):
    global SCALE, BIAS
    SCALE = v
    BIAS = v

def _step(x):
    return x * SCALE + BIAS

step = jax.jit(_step)
"""})
    # BOTH hazards in the same jitted fn are reported, not just the first
    assert any(f.rule == "PT002" and "SCALE" in f.message
               for f in findings)
    assert any(f.rule == "PT002" and "BIAS" in f.message
               for f in findings)


def test_pt002_unhashable_static_arg(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax

def f(x, cfg):
    return x

g = jax.jit(f, static_argnums=(1,))

def call(x):
    return g(x, [1, 2, 3])
"""})
    assert any(f.rule == "PT002" and "unhashable" in f.message
               for f in findings)


def test_pt002_shape_key_warning(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
_CACHE = {}

def lookup(x):
    return _CACHE[f"k{x.shape}"]
"""})
    assert any(f.rule == "PT002" and "shape" in f.message
               for f in findings)


# -- PT003: traced side effects ----------------------------------------------

def test_pt003_stats_in_jit_positive(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax
from paddle_tpu import stats

def _step(x):
    stats.add("train/steps")
    return x + 1

step = jax.jit(_step)
"""})
    assert any(f.rule == "PT003" and "stats.add" in f.message
               for f in findings)


def test_pt003_host_side_stats_negative(tmp_path):
    """stats on the host side of the dispatch is the entire point of
    the stats module — never flagged."""
    findings = _lint(tmp_path, {"mod.py": """
import jax
from paddle_tpu import stats

def _step(x):
    return x + 1

step = jax.jit(_step)

def serve_loop(x):
    y = step(x)
    stats.add("serve/steps")
    return y
"""})
    assert "PT003" not in _rules_hit(findings)


def test_pt003_local_append_negative_closure_positive(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax

LEAK = []

def _step(x):
    rows = []
    rows.append(x)      # local: idiomatic trace-time build — fine
    LEAK.append(x)      # closure/global: leaks tracers
    return rows[0]

step = jax.jit(_step)
"""})
    hits = [f for f in findings if f.rule == "PT003"]
    assert len(hits) == 1 and "LEAK" in hits[0].message


def test_pt003_suppression(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax
from paddle_tpu import stats

def _step(x):
    # ptlint: disable=PT003 -- issue-time counter, documented
    stats.add("collective/calls")
    return x

step = jax.jit(_step)
"""})
    assert "PT003" not in _rules_hit(findings)


# -- PT004: collective-order divergence --------------------------------------

def test_pt004_rank_conditional_collective_positive(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
import jax
from jax import lax

def sync(x, rank):
    if rank == 0:
        x = lax.psum(x, "dp")
    return x
"""})
    hits = [f for f in findings if f.rule == "PT004"]
    assert hits and "psum" in hits[0].message


def test_pt004_balanced_arms_negative(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
from jax import lax

def sync(x, rank):
    if rank == 0:
        x = lax.psum(x * 2, "dp")
    else:
        x = lax.psum(x, "dp")
    return x

def rank0_local_work(meta, rank):
    if rank == 0:
        meta = dict(meta)       # local-only work is fine
    return lax.psum(meta["x"], "dp")
"""})
    assert "PT004" not in _rules_hit(findings)


def test_pt004_suppression(tmp_path):
    findings = _lint(tmp_path, {"mod.py": """
from jax import lax

def sync(x, rank):
    if rank == 0:
        # ptlint: disable=PT004 -- single-rank program by construction
        x = lax.psum(x, "dp")
    return x
"""})
    assert "PT004" not in _rules_hit(findings)


# -- PT005: env contract -----------------------------------------------------

def test_pt005_undeclared_positive(tmp_path):
    findings = _lint(tmp_path, {
        "flags.py": FLAGS_SRC,
        "mod.py": """
import os

def knob():
    return os.environ.get("PT_SECRET_KNOB", "0")
"""})
    hits = [f for f in findings if f.rule == "PT005"]
    assert hits and "PT_SECRET_KNOB" in hits[0].message


def test_pt005_declared_and_prefix_negative(tmp_path):
    findings = _lint(tmp_path, {
        "flags.py": FLAGS_SRC,
        "mod.py": """
import os

def knobs():
    a = os.environ.get("PT_DECLARED_KNOB")
    b = os.environ["PT_FLAGS_SCAN_LAYERS"]
    c = os.getenv("HOME")          # non-PT_ names are out of contract
    return a, b, c
"""})
    assert "PT005" not in _rules_hit(findings)


@pytest.fixture(scope="module")
def repo_findings():
    """One full-package lint shared by the self-lint assertions."""
    project = load_project([os.path.join(REPO, "paddle_tpu")], root=REPO)
    return project, run(project)


def test_pt005_package_registry_is_complete(repo_findings):
    """Every PT_* read in the real package is declared in flags.py —
    the knob/doc contract cannot silently fork."""
    _, findings = repo_findings
    assert [f for f in findings if f.rule == "PT005"] == []


def test_env_declared_agrees_with_linter(repo_findings):
    """The runtime helper flags.env_declared() and PT005's AST-parsed
    declared set are two views of one registry — they must agree, or
    runtime checks and the lint gate drift apart."""
    import paddle_tpu.flags as flags
    project, _ = repo_findings
    names, prefixes = project._pt005_declared
    for n in names:
        assert flags.env_declared(n), n
    for p in prefixes:
        assert flags.env_declared(p + "ANYTHING"), p
    assert not flags.env_declared("PT_NOT_IN_THE_CONTRACT")


def test_pt005_tool_prefix_namespace(tmp_path):
    """declare_tool_prefix brings a tool namespace under contract: an
    undeclared PD_* read is flagged, a declared one passes, and names
    under UNregistered prefixes stay out of contract."""
    findings = _lint(tmp_path, {
        "flags.py": FLAGS_SRC + """
def declare_tool_prefix(prefix, help="", owner=""):
    pass

declare_tool_prefix("PD_", "profile_decode knobs")
declare_env("PD_SIZE", "model size")
""",
        "tool.py": """
import os

def knobs():
    a = os.environ.get("PD_SIZE", "tiny")    # declared: clean
    b = os.environ.get("PD_SECRET_KNOB")     # in-namespace, undeclared
    c = os.getenv("FLEETOBS_ANY")            # namespace not registered
    d = os.environ.get("HOME")               # out of contract
    return a, b, c, d
"""})
    hits = [f for f in findings if f.rule == "PT005"]
    assert len(hits) == 1 and "PD_SECRET_KNOB" in hits[0].message


def test_pt005_tools_tree_registry_complete():
    """tools/ is linted under the same contract (ci.sh lints
    paddle_tpu AND tools): every PD_*/FLEETOBS_*/PT_* read there must
    be declared — exercises the subtree fallback that pulls the
    registry off paddle_tpu/flags.py."""
    rules = [r for r in default_rules() if r.id == "PT005"]
    project = load_project([os.path.join(REPO, "tools")], root=REPO)
    findings = run(project, rules)
    assert [f for f in findings if f.rule == "PT005"] == []


# -- baseline round-trip -----------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    src = {"mod.py": """
import jax
import jax.numpy as jnp

def _step(x):
    return jnp.sum(x).item()

step = jax.jit(_step)
"""}
    findings = _lint(tmp_path, src)
    assert findings
    bl_path = str(tmp_path / "baseline.json")
    baseline.write(bl_path, findings)
    again = _lint(tmp_path, src)
    new, known = baseline.partition(again, baseline.load(bl_path))
    assert new == [] and len(known) == len(findings)
    # a NEW finding is not masked by the old baseline
    src["mod.py"] += """
def _other(x):
    return float(jnp.max(x))

other = jax.jit(_other)
"""
    third = _lint(tmp_path, src)
    new, known = baseline.partition(third, baseline.load(bl_path))
    assert len(known) == len(findings) and len(new) >= 1


def test_fingerprints_stable_across_line_shifts(tmp_path):
    src = """
import jax
import jax.numpy as jnp

def _step(x):
    return jnp.sum(x).item()

step = jax.jit(_step)
"""
    f1 = _lint(tmp_path, {"mod.py": src})
    f2 = _lint(tmp_path, {"mod.py": "\n# a comment\n\n" + src})
    assert [f.fingerprint for f in f1] == [f.fingerprint for f in f2]
    assert f1[0].line != f2[0].line


# -- repo self-lint gate -----------------------------------------------------

def test_repo_self_lint_zero_new_findings(repo_findings):
    project, findings = repo_findings
    assert project.parse_errors == []
    bl = baseline.load(os.path.join(REPO, "tools",
                                    "ptlint_baseline.json"))
    new, _ = baseline.partition(findings, bl)
    assert new == [], "new ptlint findings:\n" + "\n".join(
        f.format() for f in new)


def test_cli_exit_codes_and_stats(tmp_path):
    """CLI contract: 0 on the shipped tree (with --stats reporting every
    rule family), 1 once a host-sync fixture is planted."""
    cli = os.path.join(REPO, "tools", "ptlint.py")
    r = subprocess.run([sys.executable, cli, "paddle_tpu",
                        "--error-on-new", "--stats"],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    for rule in ("PT001", "PT002", "PT003", "PT004", "PT005"):
        assert rule in r.stdout
    bad = tmp_path / "planted.py"
    bad.write_text("import jax\nimport jax.numpy as jnp\n\n"
                   "def _f(x):\n    return jnp.sum(x).item()\n\n"
                   "g = jax.jit(_f)\n")
    r = subprocess.run([sys.executable, cli, str(bad)],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "PT001" in r.stdout


def test_cli_parse_error_exits_2(tmp_path):
    """An unparseable file means the tree was NOT checked — the lint
    gate must fail loudly (2), not pass green."""
    cli = os.path.join(REPO, "tools", "ptlint.py")
    broken = tmp_path / "broken.py"
    broken.write_text("def oops(:\n")
    r = subprocess.run([sys.executable, cli, str(broken)],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "could not be parsed" in r.stderr
    # --no-error keeps report-only mode green
    r = subprocess.run([sys.executable, cli, str(broken), "--no-error"],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


# -- callgraph alias resolution (ISSUE 20 satellite) -------------------------

def test_alias_does_not_smear_jit_root(tmp_path):
    """``step = self._traced; jax.jit(step)`` must root _traced — NOT
    an unrelated host-side method that happens to be named ``step``
    (the PR 19 false positive)."""
    findings = _lint(tmp_path, {"mod.py": """
import jax
import jax.numpy as jnp


class Engine:
    def _traced(self, x):
        return jnp.sum(x)

    def build(self):
        step = self._traced
        return jax.jit(step)


class Host:
    def step(self, x):
        return jnp.sum(x).item()
"""})
    assert "PT001" not in _rules_hit(findings)


def test_alias_target_still_enters_jit_scope(tmp_path):
    """Positive control: the alias TARGET is the jit root, so a host
    sync inside it is still flagged."""
    findings = _lint(tmp_path, {"mod.py": """
import jax
import jax.numpy as jnp


class Engine:
    def _traced(self, x):
        return jnp.sum(x).item()

    def build(self):
        step = self._traced
        return jax.jit(step)
"""})
    assert any(f.rule == "PT001" and f.symbol.endswith("_traced")
               for f in findings)


_MEMBER_CALL_SRC = """
import jax
import jax.numpy as jnp


class Kind:
    def step(self, x):
        return jnp.sum(x){kind_sync}


class Engine:
    def _traced(self, x):
        return self.kind.step(x)

    def build(self):
        return jax.jit(self._traced)

    def step(self, x):
        return jnp.sum(x){host_sync}
"""


@pytest.mark.parametrize("kind_sync,host_sync,flagged", [
    ("", ".item()", None), (".item()", "", "Kind.step")])
def test_member_call_is_not_the_callers_own_method(tmp_path, kind_sync,
                                                   host_sync, flagged):
    """``self.kind.step(x)`` inside traced code runs the MEMBER's
    ``step``: the caller's own host-side ``step`` stays out of trace
    scope (the decode step's layer scan calls ``self.kind.step`` beside
    the engine's scheduler ``step``), and the member's, where the file
    holds it, still enters."""
    findings = _lint(tmp_path, {"mod.py": _MEMBER_CALL_SRC.format(
        kind_sync=kind_sync, host_sync=host_sync)})
    hits = [f.symbol for f in findings if f.rule == "PT001"]
    if flagged is None:
        assert not hits, hits
    else:
        assert hits and all(h.endswith(flagged) for h in hits), hits


def test_module_level_alias_resolves(tmp_path):
    """``run = _impl`` at module level: jitting the alias roots _impl,
    and a same-named function elsewhere in the file stays host code."""
    findings = _lint(tmp_path, {"mod.py": """
import jax
import jax.numpy as jnp


def _impl(x):
    return jnp.sum(x).item()


run = _impl
traced = jax.jit(run)


def run_report(x):
    pass
"""})
    assert any(f.rule == "PT001" and f.symbol.endswith("_impl")
               for f in findings)
