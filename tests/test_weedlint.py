"""Tier-1 enforcement + self-tests for weedlint (seaweedfs_tpu/analysis).

This file replaces tests/test_async_guard.py and tests/test_timeout_guard.py:
their ast.walk logic now lives in the rule registry, and these tests
iterate that registry — adding a rule automatically adds (a) its
seeded-violation self-test and (b) its tier-1 enforcement over the tree.

Structure:
  * registry self-tests: every rule fires on its own seeded fixture and
    stays quiet on its clean fixture;
  * tree enforcement: one full engine pass over seaweedfs_tpu/ + tests/,
    then a parametrized per-rule assertion (failures name the rule);
  * engine mechanics: suppression comments, baseline round-trip, stale
    baseline entries failing loudly, fingerprint stability under line
    drift, CLI exit codes;
  * regression tests for the real findings the new analyzers surfaced
    (fd-leak comprehensions in striping/feed, fire-and-forget executor
    futures, trace-less raft/broker sessions).
"""

import asyncio
import json
import logging
import os
import subprocess
import sys
import textwrap

import pytest

from seaweedfs_tpu.analysis import (
    Baseline, check_source, load_module, registry, run,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, ".weedlint-baseline.json")
RULES = registry()
RULE_NAMES = sorted(RULES)


# ------------------------------------------------------- registry self-tests

@pytest.mark.parametrize("name", RULE_NAMES)
def test_rule_fires_on_seeded_fixture(name):
    """A rule that cannot flag its own seeded violation guards nothing."""
    rule = RULES[name]
    assert rule.fixture, f"rule {name} ships no seeded-violation fixture"
    diags = check_source(rule, rule.fixture)
    assert diags, f"rule {name} is silent on its own seeded fixture"
    for d in diags:
        assert d.rule == name and d.line >= 1 and d.message


@pytest.mark.parametrize("name", RULE_NAMES)
def test_rule_quiet_on_clean_fixture(name):
    rule = RULES[name]
    if not rule.clean_fixture:
        pytest.skip(f"rule {name} has no clean fixture")
    diags = check_source(rule, rule.clean_fixture)
    assert not diags, (f"rule {name} false-positives on its clean "
                       f"fixture: {[d.message for d in diags]}")


def test_every_rule_documents_itself():
    for name, rule in RULES.items():
        assert rule.rationale, f"rule {name} has no rationale"
        assert rule.scope, f"rule {name} has no scope"


def test_metaring_scope_pinned():
    """The metadata scale-out plane must stay inside the async-plane
    guards: a future scope edit that drops seaweedfs_tpu/metaring/ from
    any of these rules silently un-lints a whole serving plane."""
    for name in ("daemon-loop-shedable", "fault-point-registry",
                 "ctx-propagation", "async-blocking-call"):
        rule = RULES[name]
        assert rule.applies_to("seaweedfs_tpu/metaring/handoff.py"), \
            f"rule {name} no longer covers seaweedfs_tpu/metaring/"
    # and the daemon rule's explicit plane list is pinned verbatim —
    # its per-plane "guards something" check keys off these prefixes
    assert tuple(RULES["daemon-loop-shedable"].scope) == (
        "seaweedfs_tpu/lifecycle/", "seaweedfs_tpu/geo/",
        "seaweedfs_tpu/metaring/", "seaweedfs_tpu/balance/",
        "seaweedfs_tpu/clustersim/")


def test_balance_scope_pinned():
    """The balance plane moves data (a bad daemon loop stampedes volume
    servers; a leaked session pins sockets for the life of the master)
    and clustersim is the harness later scale claims are verified
    against — both must stay inside the daemon-loop / async-blocking /
    resource-leak guards. A scope edit that drops either directory
    silently un-lints the control plane."""
    for name in ("daemon-loop-shedable", "async-blocking-call",
                 "resource-leak"):
        rule = RULES[name]
        for path in ("seaweedfs_tpu/balance/daemon.py",
                     "seaweedfs_tpu/balance/planner.py",
                     "seaweedfs_tpu/clustersim/sim.py",
                     "seaweedfs_tpu/clustersim/scenarios.py"):
            assert rule.applies_to(path), \
                f"rule {name} no longer covers {path}"
    # and the balance/sim fault points must stay in the registry:
    # firing an unknown point silently no-ops the chaos drills the
    # acceptance criteria lean on
    from seaweedfs_tpu import faults
    for point in ("master.balance.plan", "master.balance.move",
                  "sim.heartbeat"):
        assert point in faults.KNOWN_POINTS, \
            f"fault point {point} dropped from faults.KNOWN_POINTS"


def test_observe_scope_pinned():
    """The telemetry plane runs inside every server's event loop: the
    profiler's sampler thread, the wide-event ring, and the ndjson sink
    must stay under the async-blocking / resource-leak / metric-family
    guards. A future scope edit that narrows any of these rules away
    from seaweedfs_tpu/observe/ silently un-lints the one plane that is
    always on in production."""
    for name in ("async-blocking-call", "resource-leak",
                 "metric-label-registry"):
        rule = RULES[name]
        for path in ("seaweedfs_tpu/observe/profiler.py",
                     "seaweedfs_tpu/observe/wideevents.py",
                     "seaweedfs_tpu/observe/__init__.py"):
            assert rule.applies_to(path), \
                f"rule {name} no longer covers {path}"


def test_fused_scope_pinned():
    """The fused warm-down pass (ec/fused.py) owns a reader pool, two
    all-or-nothing dst file handles, and three fault points fired from
    worker threads — exactly what the resource-leak / async-blocking /
    fault-point-registry guards exist for. A scope edit that narrows
    any of them away from seaweedfs_tpu/ec/fused.py silently un-lints
    the one pass that holds a volume's only compacted copy mid-flight."""
    for name in ("resource-leak", "async-blocking-call",
                 "fault-point-registry"):
        rule = RULES[name]
        assert rule.applies_to("seaweedfs_tpu/ec/fused.py"), \
            f"rule {name} no longer covers seaweedfs_tpu/ec/fused.py"
    # and the fused fault points must stay in the registry: firing an
    # unknown point is exactly what fault-point-registry exists to catch
    from seaweedfs_tpu import faults
    for point in ("ec.fused.read", "ec.fused.gzip", "ec.fused.commit"):
        assert point in faults.KNOWN_POINTS, \
            f"fault point {point} dropped from faults.KNOWN_POINTS"


def test_sharded_scope_pinned():
    """The shard runner is the one module that forks, owns a shared
    mmap segment, and renders cross-process Prometheus lines by hand —
    exactly the failure modes the async-blocking / resource-leak /
    metric-label-registry / fork-then-asyncio guards exist for. A scope
    edit that drops server/sharded.py from any of them silently
    un-lints the fleet supervisor."""
    for name in ("async-blocking-call", "resource-leak",
                 "metric-label-registry", "fork-then-asyncio"):
        rule = RULES[name]
        assert rule.applies_to("seaweedfs_tpu/server/sharded.py"), \
            f"rule {name} no longer covers seaweedfs_tpu/server/sharded.py"


def test_ops_scope_pinned():
    """The kernels (ops/rs_jax.py, ops/rs_pallas.py) run under jit on
    device buffers and sit on every EC path — what the metric-label /
    resource-leak guards exist for. A scope edit that narrows either
    away from the ops tree silently un-lints the hottest code in the
    repo."""
    for name in ("metric-label-registry", "resource-leak"):
        rule = RULES[name]
        for path in ("seaweedfs_tpu/ops/rs_jax.py",
                     "seaweedfs_tpu/ops/rs_pallas.py"):
            assert rule.applies_to(path), \
                f"rule {name} no longer covers {path}"
    # the point went with the packed-plane staging it guarded: a
    # declared point nothing fires is what fault-point-registry catches
    from seaweedfs_tpu import faults
    assert "ec.stage.pack" not in faults.KNOWN_POINTS


# ------------------------------------------------------- tree enforcement

@pytest.fixture(scope="module")
def tree_report():
    """One engine pass over the package + tests with the checked-in
    baseline (exactly what scripts/lint.sh runs in CI)."""
    return run(REPO_ROOT,
               [os.path.join(REPO_ROOT, "seaweedfs_tpu"),
                os.path.join(REPO_ROOT, "tests")],
               baseline=Baseline.load(BASELINE))


@pytest.mark.parametrize("name", RULE_NAMES + ["parse-error"])
def test_tree_clean(tree_report, name):
    """Tier-1 gate, per rule: no new findings anywhere in the tree."""
    mine = [d for d in tree_report.new if d.rule == name]
    assert not mine, "\n".join(d.render() for d in mine)


def test_tree_no_stale_baseline(tree_report):
    assert not tree_report.stale_baseline, tree_report.stale_baseline


def test_tree_scanned_everything(tree_report):
    # the gate must actually be looking at the tree (a path typo that
    # matched nothing would "pass" forever)
    assert tree_report.files_checked > 150


def test_cli_gate_matches_engine():
    """scripts/lint.sh's exact invocation exits 0 — the CI mode."""
    p = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.analysis",
         "--baseline", BASELINE, "seaweedfs_tpu/", "tests/"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "clean" in p.stdout


# ------------------------------------------------------- engine mechanics

def _write_pkg_file(tmp_path, source, rel="seaweedfs_tpu/server/bad.py"):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return path


# fires BOTH http-timeout (v1) and deadline-propagation (v2): a raw
# urlopen with no timeout and no budget laundering
_VIOLATION = """\
import urllib.request
def fetch(u):
    return urllib.request.urlopen(u)
"""
_VIOLATION_RULES = {"http-timeout", "deadline-propagation"}


def test_cli_flags_seeded_violation(tmp_path):
    _write_pkg_file(tmp_path, _VIOLATION)
    p = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.analysis",
         "--root", str(tmp_path), str(tmp_path / "seaweedfs_tpu")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "http-timeout" in p.stdout
    assert "seaweedfs_tpu/server/bad.py:3" in p.stdout


def test_cli_unknown_rule_is_usage_error(tmp_path):
    _write_pkg_file(tmp_path, _VIOLATION)
    p = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.analysis",
         "--rules", "no-such-rule", "--root", str(tmp_path),
         str(tmp_path / "seaweedfs_tpu")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert "no-such-rule" in p.stderr


def test_parse_error_is_a_finding(tmp_path):
    _write_pkg_file(tmp_path, "def broken(:\n")
    report = run(str(tmp_path), [str(tmp_path)])
    assert [d.rule for d in report.new] == ["parse-error"]


def test_suppression_inline():
    rule = RULES["http-timeout"]
    src = ("import urllib.request\n"
           "def f(u):\n"
           "    return urllib.request.urlopen(u)  "
           "# weedlint: disable=http-timeout\n")
    assert check_source(rule, src) == []


def test_suppression_on_multiline_statement_tail():
    """A trailing comment on the LAST line of a multi-line call must
    suppress the diagnostic anchored at the call's FIRST line — the
    natural placement for suppressing a multi-line ClientSession()."""
    rule = RULES["http-timeout"]
    src = ("import urllib.request\n"
           "def f(u, hdrs):\n"
           "    return urllib.request.urlopen(\n"
           "        u,\n"
           "        hdrs)  # weedlint: disable=http-timeout\n")
    assert check_source(rule, src) == []


def test_standalone_suppression_between_statements_stays_narrow():
    """A standalone comment between statements must not silence the
    whole enclosing function — only the next statement."""
    rule = RULES["http-timeout"]
    src = ("import urllib.request\n"
           "def f(u):\n"
           "    # weedlint: disable=http-timeout\n"
           "    a = urllib.request.urlopen(u)\n"
           "    b = urllib.request.urlopen(u)\n"
           "    return a, b\n")
    diags = check_source(rule, src)
    assert [d.line for d in diags] == [5]


def test_parse_error_cannot_be_baselined(tmp_path):
    """A syntax-broken file must always fail: --write-baseline refuses
    it, and a hand-forged parse-error entry neither matches nor
    lingers."""
    _write_pkg_file(tmp_path, "def broken(:\n")
    bl = str(tmp_path / "bl.json")
    p = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.analysis",
         "--root", str(tmp_path), "--baseline", bl,
         "--write-baseline", str(tmp_path / "seaweedfs_tpu")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1 and "refusing" in p.stderr
    assert not os.path.exists(bl)
    # forged entry: still fails (never matched), and goes stale
    report = run(str(tmp_path), [str(tmp_path)])
    Baseline.from_findings(report.new).write(bl)
    report2 = run(str(tmp_path), [str(tmp_path)],
                  baseline=Baseline.load(bl))
    assert report2.new and not report2.clean
    assert report2.stale_baseline  # the forged entry can't linger


def test_cancelled_swallow_reraise_first_is_clean_nested_break_is_not():
    """py3.10-accurate handler reachability: the re-raise-first idiom
    is clean; a break that only exits an inner loop is not an exit."""
    rule = RULES["cancelled-swallow"]
    clean = ("async def loop(self):\n"
             "    while True:\n"
             "        try:\n"
             "            await self._pass()\n"
             "        except asyncio.CancelledError:\n"
             "            raise\n"
             "        except BaseException:\n"
             "            log.warning('x')\n")
    assert check_source(rule, clean) == []
    bad = ("async def loop(self):\n"
           "    while True:\n"
           "        try:\n"
           "            await self._pass()\n"
           "        except BaseException:\n"
           "            for x in self.items:\n"
           "                break\n")
    assert len(check_source(rule, bad)) == 1


def test_cli_zero_files_is_usage_error(tmp_path):
    """A typo'd path (or wrong cwd) must not read as a passing gate."""
    p = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.analysis",
         "--root", str(tmp_path), str(tmp_path / "no-such-dir")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert "nothing was linted" in p.stderr


def test_suppression_on_multiline_except_header():
    """A trailing comment on the last line of a multi-line except
    clause reaches the diagnostic anchored at the except's first line."""
    rule = RULES["cancelled-swallow"]
    src = ("async def loop(self):\n"
           "    while True:\n"
           "        try:\n"
           "            await self._pass()\n"
           "        except (ValueError,\n"
           "                asyncio.CancelledError"
           "):  # weedlint: disable=cancelled-swallow\n"
           "            pass\n")
    assert check_source(rule, src) == []


def test_ctx_propagation_requires_the_blessed_config():
    """trace_configs=[] (or some other config) still drops the headers
    — only client_trace_config satisfies the rule."""
    rule = RULES["ctx-propagation"]
    src = ("import aiohttp\n"
           "def f(T):\n"
           "    return aiohttp.ClientSession(timeout=T,\n"
           "                                 trace_configs=[])\n")
    assert len(check_source(rule, src)) == 1


def test_fault_registry_reads_analyzed_tree_not_running_package(tmp_path):
    """--root on a branch checkout judges fire() sites against THAT
    tree's KNOWN_POINTS, not the installed package's."""
    _write_pkg_file(tmp_path,
                    "KNOWN_POINTS = frozenset({\n"
                    "    'branch.point',\n"
                    "})\n", rel="seaweedfs_tpu/faults/__init__.py")
    _write_pkg_file(tmp_path,
                    "from . import faults\n"
                    "async def f():\n"
                    "    await faults.fire_async('branch.point')\n"
                    "    await faults.fire_async('branch.typo')\n",
                    rel="seaweedfs_tpu/server/x.py")
    report = run(str(tmp_path), [str(tmp_path)],
                 rule_names=["fault-point-registry"])
    msgs = [d.message for d in report.new]
    assert len(msgs) == 1 and "branch.typo" in msgs[0], msgs


def test_no_duplicate_findings_in_nested_defs():
    """One violation inside a nested def is ONE finding: the scope
    walks must not report it once for the outer function and again for
    the nested one (doubled findings churn two baseline fingerprints)."""
    resources = RULES["resource-leak"]
    src = ("import os\n"
           "def outer():\n"
           "    def inner(paths):\n"
           "        fds = [os.open(p, os.O_RDONLY) for p in paths]\n"
           "        return fds\n"
           "    return inner\n")
    assert len(check_source(resources, src)) == 1
    prop = RULES["ctx-propagation"]
    src2 = ("async def outer(self, loop):\n"
            "    async def mid():\n"
            "        def work():\n"
            "            with observe.span('x'):\n"
            "                return 1\n"
            "        await loop.run_in_executor(None, work)\n"
            "    await mid()\n")
    assert len(check_source(prop, src2)) == 1


def test_suppression_standalone_line_above():
    rule = RULES["http-timeout"]
    src = ("import urllib.request\n"
           "def f(u):\n"
           "    # weedlint: disable=http-timeout\n"
           "    return urllib.request.urlopen(u)\n")
    assert check_source(rule, src) == []


def test_suppression_wrong_rule_does_not_apply():
    rule = RULES["http-timeout"]
    src = ("import urllib.request\n"
           "def f(u):\n"
           "    return urllib.request.urlopen(u)  "
           "# weedlint: disable=task-leak\n")
    assert len(check_source(rule, src)) == 1


def test_suppression_file_level_and_star():
    rule = RULES["http-timeout"]
    src = ("# weedlint: disable-file=http-timeout\n"
           "import urllib.request\n"
           "def f(u):\n"
           "    return urllib.request.urlopen(u)\n")
    assert check_source(rule, src) == []
    src_star = ("import urllib.request\n"
                "def f(u):\n"
                "    return urllib.request.urlopen(u)  "
                "# weedlint: disable=*\n")
    assert check_source(rule, src_star) == []


def test_baseline_round_trip_and_stale_entries(tmp_path):
    """New finding -> baselined -> fixed; the leftover baseline entry
    must fail the run loudly, not linger."""
    path = _write_pkg_file(tmp_path, _VIOLATION)
    bl_path = tmp_path / "bl.json"

    report = run(str(tmp_path), [str(tmp_path)])
    assert {d.rule for d in report.new} == _VIOLATION_RULES

    Baseline.from_findings(report.new).write(str(bl_path))
    report2 = run(str(tmp_path), [str(tmp_path)],
                  baseline=Baseline.load(str(bl_path)))
    assert report2.clean and len(report2.baselined) == 2

    # fix the violation (bounded AND budget-laundered): every
    # grandfathered entry is now stale
    path.write_text(
        "import urllib.request\n"
        "from seaweedfs_tpu.utils import retry\n"
        "def fetch(u):\n"
        "    return urllib.request.urlopen(\n"
        "        u, timeout=retry.cap_timeout(5))\n")
    report3 = run(str(tmp_path), [str(tmp_path)],
                  baseline=Baseline.load(str(bl_path)))
    assert not report3.new
    assert len(report3.stale_baseline) == 2
    assert not report3.clean
    assert "STALE" in report3.render()


def test_baseline_fingerprint_survives_line_drift(tmp_path):
    """Unrelated edits above a baselined finding must not invalidate
    its fingerprint (content-addressed, not line-addressed)."""
    path = _write_pkg_file(tmp_path, _VIOLATION)
    bl_path = tmp_path / "bl.json"
    report = run(str(tmp_path), [str(tmp_path)])
    Baseline.from_findings(report.new).write(str(bl_path))

    path.write_text("# a new comment\n# another\n\n" + path.read_text())
    report2 = run(str(tmp_path), [str(tmp_path)],
                  baseline=Baseline.load(str(bl_path)))
    assert report2.clean, (report2.render(),
                           [e for e in report2.stale_baseline])
    assert len(report2.baselined) == 2
    assert {d.line for d in report2.baselined} == {6}  # drifted, matched


def test_baseline_entry_for_changed_line_goes_stale(tmp_path):
    """Editing the flagged line itself re-opens the finding: the old
    entry goes stale AND the new shape is a new finding."""
    path = _write_pkg_file(tmp_path, _VIOLATION)
    bl_path = tmp_path / "bl.json"
    Baseline.from_findings(
        run(str(tmp_path), [str(tmp_path)]).new).write(str(bl_path))
    path.write_text("import urllib.request\n"
                    "def fetch(u, extra):\n"
                    "    return urllib.request.urlopen(u or extra)\n")
    report = run(str(tmp_path), [str(tmp_path)],
                 baseline=Baseline.load(str(bl_path)))
    # both rules re-open on the edited line; both old entries go stale
    assert len(report.new) == 2 and len(report.stale_baseline) == 2


def test_baseline_entry_for_deleted_file_goes_stale(tmp_path):
    """An entry whose file was deleted is stale on any run covering its
    directory — it must not linger and silently re-grandfather the
    violation if the file ever comes back."""
    path = _write_pkg_file(tmp_path, _VIOLATION)
    bl_path = tmp_path / "bl.json"
    Baseline.from_findings(
        run(str(tmp_path), [str(tmp_path)]).new).write(str(bl_path))
    path.unlink()
    report = run(str(tmp_path), [str(tmp_path)],
                 baseline=Baseline.load(str(bl_path)))
    assert len(report.stale_baseline) == 2 and not report.clean


def test_write_baseline_subset_preserves_out_of_scope(tmp_path):
    """--write-baseline under --rules (or a path subset) only replaces
    entries it re-judged; grandfathered findings of other rules/paths
    survive the rewrite."""
    _write_pkg_file(tmp_path, _VIOLATION)
    _write_pkg_file(tmp_path,
                    "async def bad():\n"
                    "    asyncio.create_task(bad())\n",
                    rel="seaweedfs_tpu/server/leaky.py")
    bl = str(tmp_path / "bl.json")
    pkg = str(tmp_path / "seaweedfs_tpu")
    base_cmd = [sys.executable, "-m", "seaweedfs_tpu.analysis",
                "--root", str(tmp_path), "--baseline", bl]
    p = subprocess.run(base_cmd + ["--write-baseline", pkg],
                       cwd=REPO_ROOT, capture_output=True, text=True,
                       timeout=120)
    assert "wrote 3 entries" in p.stdout, p.stdout + p.stderr
    # subset rewrite: only http-timeout re-judged; task-leak and
    # deadline-propagation entries preserved
    p = subprocess.run(base_cmd + ["--write-baseline",
                                   "--rules", "http-timeout", pkg],
                       cwd=REPO_ROOT, capture_output=True, text=True,
                       timeout=120)
    assert "wrote 3 entries" in p.stdout and "preserved" in p.stdout
    p = subprocess.run(base_cmd + [pkg], cwd=REPO_ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr


def test_identical_lines_fingerprint_distinctly(tmp_path):
    """Two byte-identical violations must get distinct fingerprints
    (occurrence-indexed), so baselining one does not hide the other."""
    src = ("import urllib.request\n"
           "def f(u):\n"
           "    return urllib.request.urlopen(u)\n"
           "def g(u):\n"
           "    return urllib.request.urlopen(u)\n")
    _write_pkg_file(tmp_path, src)
    report = run(str(tmp_path), [str(tmp_path)])
    fps = [d.fingerprint for d in report.new
           if d.rule == "http-timeout"]
    assert len(fps) == 2 and len(set(fps)) == 2


# ------------------------------------------------ v2: inter-procedural layer

def test_suppression_reaches_decorator_line_finding():
    """A finding anchored at a DECORATOR line is suppressible from
    anywhere in the decorated statement's header — the decorator lines
    are part of the logical statement (pre-fix, they belonged to no
    span, so a trailing comment on the multi-line decorator's last
    line, or on the def line, never reached the anchor)."""
    rule = RULES["http-timeout"]
    base = ("import functools\n"
            "import urllib.request\n"
            "@functools.lru_cache(\n"
            "    urllib.request.urlopen('http://x'){comment})\n"
            "def f():\n"
            "    pass\n")
    # finding anchors at line 4 (the urlopen call)
    assert [d.line for d in
            check_source(rule, base.format(comment=""))] == [4]
    # trailing comment on the decorator's closing line reaches it
    assert check_source(rule, base.format(
        comment=",  # weedlint: disable=http-timeout\n")) == []


def test_decorator_line_finding_suppressed_from_def_line():
    rule = RULES["http-timeout"]
    src = ("import functools\n"
           "import urllib.request\n"
           "@functools.lru_cache(urllib.request.urlopen('http://x'))\n"
           "def f():  # weedlint: disable=http-timeout\n"
           "    pass\n")
    assert check_source(rule, src) == []


def test_blocking_call_transitive_depth():
    """The chain report names every hop; laundering is structural
    (helpers handed to run_in_executor never form an edge)."""
    rule = RULES["blocking-call-transitive"]
    src = ("import os\n"
           "def a(fd):\n"
           "    b(fd)\n"
           "def b(fd):\n"
           "    c(fd)\n"
           "def c(fd):\n"
           "    os.fsync(fd)\n"
           "async def handler(self, fd):\n"
           "    a(fd)\n")
    diags = check_source(rule, src)
    assert len(diags) == 1 and diags[0].line == 9
    assert "a (" in diags[0].message and "c (" in diags[0].message
    assert "os.fsync()" in diags[0].message


def test_blocking_call_transitive_through_a_cycle():
    """Recursive helpers must not poison the memo: with a<->b mutually
    recursive and a also reaching fsync, BOTH async roots report —
    a cycle-truncated negative cached for b would hide h2's chain."""
    rule = RULES["blocking-call-transitive"]
    src = ("import os\n"
           "def a(fd):\n"
           "    b(fd)\n"
           "    c(fd)\n"
           "def b(fd):\n"
           "    a(fd)\n"
           "def c(fd):\n"
           "    os.fsync(fd)\n"
           "async def h1(self, fd):\n"
           "    a(fd)\n"
           "async def h2(self, fd):\n"
           "    b(fd)\n")
    diags = check_source(rule, src)
    assert sorted(d.line for d in diags) == [10, 12], \
        [(d.line, d.message) for d in diags]


def test_blocking_call_transitive_no_loop_fallback_is_clean():
    """The except-RuntimeError-after-loop-probe idiom (raft's
    _schedule_flush) runs off-loop by construction and must not taint
    chains."""
    rule = RULES["blocking-call-transitive"]
    src = ("import asyncio\n"
           "import os\n"
           "def save(self, fd):\n"
           "    os.fsync(fd)\n"
           "def schedule(self, fd):\n"
           "    try:\n"
           "        asyncio.ensure_future(self.flush())\n"
           "    except RuntimeError:\n"
           "        save(self, fd)\n"
           "async def caller(self, fd):\n"
           "    self.schedule(fd)\n")
    assert check_source(rule, src) == []


def test_blocking_call_transitive_through_methods_across_classes():
    rule = RULES["blocking-call-transitive"]
    src = ("import time\n"
           "class Store:\n"
           "    def compact(self):\n"
           "        time.sleep(1)\n"
           "class Server:\n"
           "    def __init__(self):\n"
           "        self.store = Store()\n"
           "    def _sync_compact(self):\n"
           "        return Store.compact(self)\n"
           "    async def handler(self):\n"
           "        self._sync_compact()\n")
    diags = check_source(rule, src)
    assert len(diags) == 1 and "time.sleep" in diags[0].message


def test_lock_ordering_call_mediated_cycle():
    """A helper that takes lock B, called under lock A in one module's
    view, plus the lexical B-under-A nesting elsewhere = cycle, with
    the via-function named."""
    rule = RULES["lock-ordering"]
    src = ("class S:\n"
           "    def lexical(self):\n"
           "        with self._a_lock:\n"
           "            with self._b_lock:\n"
           "                pass\n"
           "    def helper(self):\n"
           "        with self._a_lock:\n"
           "            pass\n"
           "    def mediated(self):\n"
           "        with self._b_lock:\n"
           "            self.helper()\n")
    diags = check_source(rule, src)
    assert diags, "call-mediated cycle missed"
    assert any("via" in d.message for d in diags)


def test_lock_held_await_transitive_generator_shape():
    rule = RULES["lock-held-await-transitive"]
    src = ("def locked_iter(self):\n"
           "    with self._lock:\n"
           "        yield from self._items\n"
           "async def consumer(self):\n"
           "    for x in locked_iter(self):\n"
           "        await self.handle(x)\n")
    diags = check_source(rule, src)
    assert len(diags) == 1 and diags[0].line == 5
    assert "yields while holding" in diags[0].message


def test_deadline_propagation_laundering_forms():
    """inject_deadline OR cap_timeout anywhere on the function
    satisfies the budget contract; entry-point planes (shell/) are out
    of scope."""
    rule = RULES["deadline-propagation"]
    capped = ("import urllib.request\n"
              "from ..utils import retry\n"
              "def external(url, t):\n"
              "    return urllib.request.urlopen(\n"
              "        url, timeout=retry.cap_timeout(t))\n")
    assert check_source(rule, capped) == []
    shell_src = ("import urllib.request\n"
                 "def cmd(url):\n"
                 "    return urllib.request.urlopen(url, timeout=5)\n")
    assert check_source(rule, shell_src,
                        relpath="seaweedfs_tpu/shell/x_commands.py") == []
    assert len(check_source(rule, shell_src)) == 1  # server plane: fires


def test_resource_leak_interproc_transitive_factory():
    """A function returning another factory's result is itself a
    factory (the closure follows returns-of-calls)."""
    rule = RULES["resource-leak-interproc"]
    src = ("def raw(p):\n"
           "    return open(p, 'rb')\n"
           "def wrapped(p):\n"
           "    return raw(p)\n"
           "def bad(p):\n"
           "    fh = wrapped(p)\n"
           "    data = fh.read()\n"
           "    fh.close()\n"
           "    return data\n")
    diags = check_source(rule, src)
    assert len(diags) == 1 and diags[0].line == 6
    assert "happy path" in diags[0].message


def test_jobs_parallel_parse_identical_findings(tmp_path):
    """--jobs N must produce byte-identical findings and fingerprints
    to the serial run (deterministic order is part of the contract)."""
    for i in range(6):
        _write_pkg_file(tmp_path, _VIOLATION,
                        rel=f"seaweedfs_tpu/server/bad{i}.py")
    serial = run(str(tmp_path), [str(tmp_path)], jobs=1)
    parallel = run(str(tmp_path), [str(tmp_path)], jobs=4)
    ser = [(d.rule, d.path, d.line, d.fingerprint) for d in serial.new]
    par = [(d.rule, d.path, d.line, d.fingerprint) for d in parallel.new]
    assert ser == par and len(ser) == 6 * len(_VIOLATION_RULES)


def test_cli_github_format_annotations(tmp_path):
    _write_pkg_file(tmp_path, _VIOLATION)
    p = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.analysis",
         "--format", "github", "--root", str(tmp_path),
         str(tmp_path / "seaweedfs_tpu")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    assert "::error file=seaweedfs_tpu/server/bad.py,line=3," in p.stdout
    assert "title=weedlint http-timeout::" in p.stdout


def test_cli_jobs_flag(tmp_path):
    _write_pkg_file(tmp_path, _VIOLATION)
    p = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.analysis", "--jobs", "2",
         "--root", str(tmp_path), str(tmp_path / "seaweedfs_tpu")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1 and "http-timeout" in p.stdout


# ---------------------------------------------- legacy walker parity checks

def test_blocking_walker_handles_aliases():
    """Port of test_async_guard.test_guard_walker_catches_violations:
    direct calls, aliased modules and from-imports all resolve; nested
    sync defs (executor bodies) stay exempt."""
    rule = RULES["async-blocking-call"]
    src = ("import os\n"
           "import time as t\n"
           "from time import sleep as zzz\n"
           "async def bad1(fd):\n"
           "    os.fsync(fd)\n"
           "async def bad2():\n"
           "    t.sleep(1)\n"
           "async def bad3():\n"
           "    zzz(2)\n"
           "async def good(loop, fd):\n"
           "    def _sync():\n"
           "        os.fsync(fd)\n"
           "    await loop.run_in_executor(None, _sync)\n")
    lines = sorted(d.line for d in check_source(rule, src))
    assert lines == [5, 7, 9]


def test_timeout_walker_line_parity():
    """Port of test_timeout_guard.test_timeout_walker_catches_violations
    (same source, same flagged lines)."""
    rule = RULES["http-timeout"]
    src = ("import urllib.request\n"
           "import aiohttp\n"
           "import http.client\n"
           "from aiohttp import ClientSession\n"
           "def bad1(u):\n"
           "    return urllib.request.urlopen(u)\n"
           "def bad2():\n"
           "    return aiohttp.ClientSession()\n"
           "def bad3(h):\n"
           "    return http.client.HTTPConnection(h)\n"
           "def bad4():\n"
           "    return ClientSession()\n"
           "def good1(u):\n"
           "    return urllib.request.urlopen(u, timeout=5)\n"
           "def good2():\n"
           "    return aiohttp.ClientSession(timeout=object())\n"
           "def good3(h, kw):\n"
           "    return http.client.HTTPConnection(h, **kw)\n")
    lines = sorted(d.line for d in check_source(rule, src))
    assert lines == [6, 8, 10, 12]


def test_import_walker_parity():
    """Port of test_async_guard.test_import_guard_walker_catches_
    violations: stdlib flagged, package-relative/third-party/executor-
    nested exempt."""
    rule = RULES["async-stdlib-import"]
    src = ("import os\n"
           "async def bad():\n"
           "    import uuid\n"
           "    from time import sleep\n"
           "async def good(loop):\n"
           "    from ..utils import cipher\n"
           "    from aiohttp import web\n"
           "    def _sync():\n"
           "        import json\n"
           "    await loop.run_in_executor(None, _sync)\n")
    msgs = sorted(d.message for d in check_source(rule, src))
    assert len(msgs) == 2
    assert "time" in msgs[0] and "uuid" in msgs[1]


def test_application_walker_parity():
    """Port of test_async_guard.test_application_guard_walker_catches_
    violations for the client_max_size half."""
    rule = RULES["app-client-max-size"]
    good = ("app = web.Application(client_max_size=1,\n"
            "    middlewares=[trace, overload.admission_middleware(c)])\n")
    bad = "app = web.Application(middlewares=[trace])\n"
    assert check_source(rule, good) == []
    assert len(check_source(rule, bad)) == 1


def test_daemon_loop_walker_parity():
    """Port of test_async_guard.test_lifecycle_loop_guard_walker_
    catches_violations: bg-less + lockstep both flagged; compliant and
    bare-name variants accepted."""
    rule = RULES["daemon-loop-shedable"]
    bad = ("async def loop():\n"
           "    while True:\n"
           "        await asyncio.sleep(60)\n")
    assert len(check_source(rule, bad)) == 2  # unshedable AND lockstep
    good = ("async def loop(self):\n"
            "    overload.set_priority(overload.CLASS_BG)\n"
            "    while True:\n"
            "        await asyncio.sleep(jittered(self.cfg.interval))\n")
    assert check_source(rule, good) == []
    good2 = ("async def loop(self):\n"
             "    with priority(CLASS_BG):\n"
             "        while True:\n"
             "            await asyncio.sleep(lifecycle.jittered(3.0))\n")
    assert check_source(rule, good2) == []


def test_serving_surfaces_list_is_complete():
    """Every file constructing web.Application is in SERVING_SURFACES
    and every listed surface still exists — the completeness the legacy
    guard enforced, now via the project rule over the real tree."""
    from seaweedfs_tpu.analysis.rules.app_construction import \
        SERVING_SURFACES
    for rel in SERVING_SURFACES:
        assert os.path.exists(os.path.join(REPO_ROOT, rel)), rel


# ------------------------------------------- regressions for fixed findings

def test_open_all_closes_on_partial_failure(tmp_path, monkeypatch):
    """striping's shard-file opens are all-or-nothing: a failure on
    file N closes files 0..N-1 (the old comprehension leaked them)."""
    from seaweedfs_tpu.ec import striping

    for i in range(3):
        (tmp_path / f"s{i}").write_bytes(b"x")
    paths = [str(tmp_path / f"s{i}") for i in range(3)]
    paths.append(str(tmp_path / "missing"))

    opened = []
    real_open = open

    def tracking_open(path, mode="r", *a, **kw):
        f = real_open(path, mode, *a, **kw)
        opened.append(f)
        return f

    monkeypatch.setattr("builtins.open", tracking_open)
    with pytest.raises(FileNotFoundError):
        striping._open_all(paths, "rb")
    assert len(opened) == 3
    assert all(f.closed for f in opened)


class _StubCoder:
    def __init__(self, g):
        self.k, self.m = g.data_shards, g.parity_shards

    def reconstruct(self, shards):  # never reached in the error test
        raise AssertionError("unused")


def test_rebuild_inputs_closed_when_output_open_fails(tmp_path,
                                                      monkeypatch):
    """rebuild_ec_files closes the already-opened survivor inputs when
    opening an output shard fails (ENOSPC injected): the pre-fix code
    leaked every input fd on that path."""
    from seaweedfs_tpu.ec import striping

    g = striping.DEFAULT
    base = str(tmp_path / "v")
    for i in range(g.data_shards):   # k survivors, parity missing
        with open(base + striping.to_ext(i), "wb"):
            pass

    opened = []
    real_open = open

    def tracking_open(path, mode="r", *a, **kw):
        if "w" in mode:
            raise OSError(28, "No space left on device")
        f = real_open(path, mode, *a, **kw)
        opened.append(f)
        return f

    monkeypatch.setattr("builtins.open", tracking_open)
    with pytest.raises(OSError):
        striping.rebuild_ec_files(base, coder=_StubCoder(g))
    assert len(opened) == g.data_shards
    assert all(f.closed for f in opened), \
        "survivor inputs leaked when output open failed"


@pytest.mark.skipif(not os.path.isdir("/proc/self/fd"),
                    reason="needs procfs to count live fds")
def test_shard_feed_closes_fds_on_partial_open_failure(tmp_path):
    """ShardFeed.__init__ failing on survivor N must close the fds it
    already opened — __init__ raising means close() can never run."""
    from seaweedfs_tpu.ec.feed import ShardFeed

    def live_fds():
        return set(os.listdir("/proc/self/fd"))

    paths = []
    for i in range(3):
        p = tmp_path / f"shard{i}"
        p.write_bytes(b"abcd" * 4)
        paths.append(str(p))
    paths.insert(2, str(tmp_path / "gone"))  # 3rd open fails

    before = live_fds()
    with pytest.raises(FileNotFoundError):
        ShardFeed(paths, width=4)
    assert live_fds() == before, "leaked fds on ShardFeed error path"


class _ListHandler(logging.Handler):
    """Captures records off the glog logger directly — glog.setup()
    rewires the ROOT handlers, so pytest's caplog handler can vanish."""

    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


def _glog_capture():
    h = _ListHandler()
    logging.getLogger("seaweedfs_tpu").addHandler(h)
    return h


def test_watch_future_surfaces_background_error():
    """The fire-and-forget executor futures (filer disk-cache put,
    master sequencer set_max) now route through glog.watch_future: the
    exception is retrieved and logged instead of vanishing."""
    from seaweedfs_tpu.utils import glog

    def boom():
        raise RuntimeError("disk full")

    async def main():
        loop = asyncio.get_event_loop()
        fut = glog.watch_future(
            loop.run_in_executor(None, boom), "chunk-cache disk put X")
        with pytest.raises(RuntimeError):
            await fut   # the caller-visible path still works
        await asyncio.sleep(0)   # let the done callback run

    h = _glog_capture()
    try:
        asyncio.run(main())
    finally:
        logging.getLogger("seaweedfs_tpu").removeHandler(h)
    assert any("chunk-cache disk put X" in r.getMessage()
               and "disk full" in r.getMessage() for r in h.records)


def test_watch_future_quiet_on_success_and_cancel():
    from seaweedfs_tpu.utils import glog

    async def main():
        loop = asyncio.get_event_loop()
        await glog.watch_future(loop.run_in_executor(None, lambda: 1),
                                "ok path")
        fut = loop.create_future()
        glog.watch_future(fut, "cancelled path")
        fut.cancel()
        await asyncio.sleep(0)

    h = _glog_capture()
    try:
        asyncio.run(main())
    finally:
        logging.getLogger("seaweedfs_tpu").removeHandler(h)
    assert not [r for r in h.records
                if "background" in r.getMessage()]


def test_raft_session_carries_trace_config():
    """Raft peer fan-out joins the ambient trace: the session installs
    observe.client_trace_config() (the fixed ctx-propagation finding)."""
    from seaweedfs_tpu.cluster.raft import RaftNode

    async def main():
        node = RaftNode("127.0.0.1:9999", [], apply_fn=lambda e: None)
        await node.start()
        try:
            assert node._session._trace_configs, \
                "raft session lost its trace config"
        finally:
            await node.stop()   # closes the session

    asyncio.run(main())


def test_broker_session_carries_trace_config():
    from seaweedfs_tpu.messaging.broker import BrokerServer

    async def main():
        b = BrokerServer()
        await b._on_startup(None)
        try:
            assert b._session._trace_configs, \
                "broker session lost its trace config"
        finally:
            await b._on_cleanup(None)

    asyncio.run(main())


def test_fault_registry_matches_fired_points():
    """faults.KNOWN_POINTS and the tree agree (the rule enforces this;
    this is the direct runtime view so a failure names the drift)."""
    from seaweedfs_tpu import faults
    from seaweedfs_tpu.analysis.rules.registries import _fire_sites

    fired = set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(REPO_ROOT, "seaweedfs_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if not name.endswith(".py"):
                continue
            full = os.path.join(dirpath, name)
            mod = load_module(full, os.path.relpath(full, REPO_ROOT))
            fired |= {p for p, _, _ in _fire_sites(mod)}
    assert fired == set(faults.KNOWN_POINTS), (
        f"undeclared: {sorted(fired - faults.KNOWN_POINTS)}; "
        f"dead: {sorted(faults.KNOWN_POINTS - fired)}")


def test_baseline_file_is_checked_in_and_valid():
    with open(BASELINE) as f:
        data = json.load(f)
    assert data["version"] == 1
    assert isinstance(data["entries"], list)
