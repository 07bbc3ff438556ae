"""Graftlint tier-1 tests: the repo stays clean, the fixture corpus
stays detected, the baseline stays honest — all pure AST (no JAX work),
so this whole module costs a few seconds of AST walking, no compiles.

The nightly --strict invocation (warnings fail too) is the slow+nightly
subprocess test at the bottom — the sibling of scripts/obs_smoke.py's
lane, and marked `slow` as well so `-m 'not slow'` (which overrides the
addopts nightly exclusion) doesn't pull it into quick iteration.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from dotaclient_tpu.analysis import lint_repo, load_baseline
from dotaclient_tpu.analysis.core import RULES

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "lint_fixtures")
BASELINE = os.path.join(REPO_ROOT, "dotaclient_tpu", "analysis", "baseline.json")


# ---------------------------------------------------------------- repo gate


def test_repo_lints_clean():
    """The CI gate in-process: no new errors, no stale baseline, no
    reason-less suppressions anywhere in the package."""
    report = lint_repo(REPO_ROOT)
    assert report.files_scanned > 50  # the whole package, not a subdir
    assert report.failures(strict=False) == []


def test_repo_lints_clean_under_strict():
    """Warnings would fail the nightly lane; keep the repo warning-free
    too (there is a baseline for the day that becomes impractical)."""
    report = lint_repo(REPO_ROOT)
    assert report.failures(strict=True) == []


def test_lint_script_runs_without_jax(tmp_path):
    """The tier-1 lint must work with no JAX import: the conftest of
    this suite imports jax for every in-process test, so the proof runs
    in a subprocess."""
    code = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {REPO_ROOT!r})
        from dotaclient_tpu.analysis import lint_repo
        report = lint_repo({REPO_ROOT!r})
        assert not report.failures(), report.failures()
        assert "jax" not in sys.modules, "linting imported jax"
        assert "numpy" not in sys.modules, "linting imported numpy"
        """
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# ------------------------------------------------------------ fixture corpus


def _fixture_report():
    return lint_repo(FIXTURES)


def test_every_rule_fires_on_the_bad_corpus():
    report = _fixture_report()
    fired = {f.rule for f in report.findings}
    expected = {
        "THR001",
        "THR002",
        "JAX001",
        "JAX002",
        "JAX003",
        "OBS001",
        "OBS002",
        "OBS003",
        "LIF001",
        "LIF002",
        "WIRE001",
        "SVC001",
        "SVC002",
        "SVC003",
        "SVC004",
    }
    assert expected <= fired, f"rules that never fired: {expected - fired}"
    # every registered code rule is exercised by the corpus
    assert expected == set(RULES), "corpus out of sync with the rule registry"


def test_good_corpus_is_clean():
    report = _fixture_report()
    noisy = [
        f.render()
        for f in report.findings
        if "good" in os.path.basename(f.path)
    ]
    assert noisy == [], noisy


def test_suppression_without_reason_is_itself_an_error():
    report = _fixture_report()
    assert any(
        f.rule == "GRAFT000" and "thr_bad" in f.path for f in report.invalid
    )
    # and it did NOT suppress the underlying finding
    assert any(
        f.rule == "THR001" and "total_suppressed_badly" in f.context
        for f in report.findings
    )


def test_suppression_syntax_in_docstring_is_not_parsed():
    """Prose MENTIONING the disable syntax (docstrings, string
    literals) must neither suppress nor GRAFT000-fail — only genuine
    comment tokens are suppressions."""
    from dotaclient_tpu.analysis.core import Suppressions

    src = (
        '"""Docs: a bare graftlint: disable=THR001 does not suppress."""\n'
        'msg = "see # graftlint: disable=JAX001 in the README"\n'
        "x = 1  # graftlint: disable=OBS001(a real comment suppression)\n"
    )
    sup = Suppressions(src)
    assert sup.missing_reason == []  # the docstring+string forms: ignored
    assert not sup.covers("THR001", 1)
    assert not sup.covers("JAX001", 2)
    assert sup.covers("OBS001", 3)  # the genuine comment still works


def test_specific_known_bad_lines():
    """Spot-check that findings land on the labeled lines, not just
    somewhere in the file (guards against the visitor drifting)."""
    report = _fixture_report()
    by_rule = {}
    for f in report.findings:
        by_rule.setdefault((f.rule, os.path.basename(f.path)), []).append(f)
    jax001 = by_rule[("JAX001", "jax_bad.py")]
    # item/float/asarray/print/device_get/mixed-shape-float/int-marker
    assert len(jax001) == 7
    thr002 = by_rule[("THR002", "thr_bad.py")]
    # two distinct cycles, each reported once: the reversed pair and the
    # 3-lock A→B→C→A cycle in which no single pair is ever reversed
    assert len(thr002) == 2
    assert any("ThreeLockCycle" in f.context for f in thr002)
    # multi-worker plain-assign read-modify-write is not atomic
    assert any(
        "LostUpdateCounter" in f.context
        for f in by_rule[("THR001", "thr_bad.py")]
    )
    obs002 = by_rule[("OBS002", "learner-fixture.yaml")]
    # the learner container's unknown arg + its env-nested flag fire
    # (enclosing-block inheritance); the sidecar's --web.listen-address
    # and --config are another binary's namespace and must NOT
    flagged = {f.message.split(" ", 1)[0] for f in obs002}
    assert flagged == {"--no_such_flag", "--bogus_env_flag"}, obs002
    # the scripts/ half of OBS002: the argv list naming a known binary
    # fires on its unknown flag; the self-reinvocation list (no module
    # string) stays out of scope
    obs002_s = by_rule[("OBS002", "spawn_fixture.py")]
    flagged_s = {f.message.split(" ", 1)[0] for f in obs002_s}
    assert flagged_s == {"--not_a_learner_flag"}, obs002_s
    # LIF001: all six shapes — leak, raise-edge leak, double release,
    # second-acquire leak, release-before-retire, wrong-object fence
    # (the prefetch-lane rule: the block_until_ready must cover THIS
    # batch's put result) — each on its labeled method
    lif001 = {f.context for f in by_rule[("LIF001", "lif_bad.py")]}
    assert lif001 == {
        "LeakyPacker.pack_leak",
        "LeakyPacker.pack_raise_leak",
        "LeakyPacker.pack_double_release",
        "DoubleBufferPacker.pack_pair",
        "EarlyReleaseFetcher.fetch",
        "WrongFenceFetcher.fetch",
    }, lif001
    # LIF002: the drain-invisible queue AND the flag-less popper
    lif002 = by_rule[("LIF002", "lif_bad.py")]
    assert any("self._side" in f.message for f in lif002)
    assert any("in-flight flag" in f.message for f in lif002)
    # WIRE001: the fixture packer.cc deliberately drifts kWireBf16
    wire = by_rule[("WIRE001", "packer.cc")]
    assert any("wire code bf16: 3 (py) vs 4 (cc)" in f.message for f in wire)


def test_bad_snippet_introduced_into_package_fails(tmp_path):
    """Acceptance bar: copy a known-bad fixture into a package tree and
    the CLI exits non-zero, naming the new violation."""
    pkg = tmp_path / "dotaclient_tpu"
    pkg.mkdir()
    shutil.copy(
        os.path.join(FIXTURES, "dotaclient_tpu", "thr_bad.py"),
        pkg / "sneaky.py",
    )
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "lint_graft.py"),
            "--root",
            str(tmp_path),
            "--json",
            str(pkg),
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert not payload["ok"]
    assert any("THR001" in line for line in payload["new"])


def test_subset_lint_keeps_repo_rules_honest():
    """Linting one file must not flood OBS003 false positives: an
    explicit paths subset still analyzes the whole package for
    cross-file rules (flag consumption, lock order, stale baseline) and
    restricts only the REPORT to the requested files."""
    target = os.path.join(REPO_ROOT, "dotaclient_tpu", "obs", "http.py")
    report = lint_repo(REPO_ROOT, paths=[target])
    assert report.files_scanned == 1
    assert report.failures(strict=True) == []


# ---------------------------------------------------------------- baseline


def test_baseline_entries_all_carry_reasons():
    reasons, errors = load_baseline(BASELINE)
    assert errors == []
    assert all(r.strip() for r in reasons.values())


def test_write_baseline_pins_warnings_for_strict(tmp_path):
    """--write-baseline must pin warning-severity findings too —
    otherwise the nightly --strict lane stays red after the documented
    regenerate-and-audit workflow."""
    pkg = tmp_path / "dotaclient_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text(
        textwrap.dedent(
            """
            from dataclasses import dataclass

            @dataclass
            class MiniConfig:
                never_read_anywhere: int = 0
            """
        )
    )
    script = os.path.join(REPO_ROOT, "scripts", "lint_graft.py")
    base = [sys.executable, script, "--root", str(tmp_path)]
    run = lambda extra: subprocess.run(  # noqa: E731
        base + extra, capture_output=True, text=True, timeout=120
    )
    assert run(["--strict"]).returncode == 1  # the OBS003 warning
    assert run(["--write-baseline", "pin for test"]).returncode == 0
    proc = run(["--strict"])
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_fingerprints_survive_line_shifts(tmp_path):
    """The baseline contract: inserting lines above a finding must not
    change its fingerprint (messages carry no line numbers)."""
    src = open(os.path.join(FIXTURES, "dotaclient_tpu", "thr_bad.py")).read()
    before = _lint_source(tmp_path, src)
    shutil.rmtree(tmp_path / "dotaclient_tpu")
    after = _lint_source(tmp_path, "# pad\n# pad\n# pad\n" + src)
    fp = lambda r: sorted(f.fingerprint() for f in r.findings)
    assert fp(before) == fp(after)


def test_stale_baseline_entry_fails(tmp_path):
    """An entry whose finding no longer exists must fail the gate — the
    ratchet only tightens."""
    fake = tmp_path / "baseline.json"
    fake.write_text(
        json.dumps(
            {
                "entries": {
                    "THR001:dotaclient_tpu/gone.py:Gone.reader:deadbeef00": {
                        "reason": "was real once"
                    }
                }
            }
        )
    )
    report = lint_repo(REPO_ROOT, baseline_path=str(fake))
    assert report.stale_baseline, "stale entry not detected"
    assert any("stale" in msg for msg in report.failures())


def test_baseline_pins_findings(tmp_path):
    """A baselined finding doesn't fail the gate; removing the code
    makes the entry stale. Exercised against the fixture corpus so the
    real baseline can stay empty."""
    report = lint_repo(FIXTURES)
    pinned = next(f for f in report.findings if f.rule == "THR001")
    fake = tmp_path / "baseline.json"
    fake.write_text(
        json.dumps({"entries": {pinned.fingerprint(): {"reason": "pinned for test"}}})
    )
    repinned = lint_repo(FIXTURES, baseline_path=str(fake))
    assert pinned.fingerprint() not in {f.fingerprint() for f in repinned.findings}
    assert any(f.fingerprint() == pinned.fingerprint() for f in repinned.baselined)


def test_baselined_finding_gaining_suppression_is_not_stale(tmp_path):
    """Following the documented workflow — adding a reasoned inline
    suppression to a finding that is ALSO baselined — must not fail the
    gate with a misleading 'stale (no current finding)': the finding
    still exists, it is suppressed. (Dropping the now-redundant
    baseline entry is then a cleanup, not an emergency.)"""
    corpus = tmp_path / "corpus"
    pkg = corpus / "dotaclient_tpu"
    pkg.mkdir(parents=True)
    src = (
        "import threading\n"
        "\n"
        "\n"
        "class Torn:\n"
        "    def __init__(self):\n"
        "        self._latest = None\n"
        "        self._t = None\n"
        "\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "\n"
        "    def _run(self):\n"
        "        self._latest = (0, {})\n"
        "\n"
        "    def latest(self):\n"
        "        if self._latest is not None:\n"
        "            return self._latest[1]\n"
        "        return {}\n"
    )
    (pkg / "mod.py").write_text(src)
    first = lint_repo(str(corpus), paths=[str(pkg)])
    pinned = next(f for f in first.findings if f.rule == "THR001")
    fake = tmp_path / "baseline.json"
    fake.write_text(
        json.dumps({"entries": {pinned.fingerprint(): {"reason": "pinned"}}})
    )
    # now suppress the same finding inline, with a reason
    (pkg / "mod.py").write_text(
        src.replace(
            "        if self._latest is not None:\n",
            "        if self._latest is not None:"
            "  # graftlint: disable=THR001(test: known-benign)\n",
        )
    )
    after = lint_repo(
        str(corpus), paths=[str(pkg)], baseline_path=str(fake)
    )
    assert after.stale_baseline == [], after.stale_baseline
    assert any(f.fingerprint() == pinned.fingerprint() for f in after.suppressed)
    assert not any(
        f.fingerprint() == pinned.fingerprint() for f in after.findings
    )


# ------------------------------------------------------- atomic-read nuance


def _lint_source(tmp_path, source: str):
    pkg = tmp_path / "dotaclient_tpu"
    pkg.mkdir(exist_ok=True)
    mod = pkg / "mod.py"
    mod.write_text(textwrap.dedent(source))
    return lint_repo(str(tmp_path), paths=[str(pkg)])


def test_atomic_tuple_single_read_is_clean(tmp_path):
    report = _lint_source(
        tmp_path,
        """
        import threading

        class L:
            def __init__(self):
                self._latest = (-1, {})
                self._t = None
            def start(self):
                self._t = threading.Thread(target=self._run)
            def _run(self):
                self._latest = (0, {"a": 1.0})
            def latest(self):
                return dict(self._latest[1])
        """,
    )
    assert [f.render() for f in report.findings] == []


def test_double_read_of_rebound_attr_is_flagged(tmp_path):
    report = _lint_source(
        tmp_path,
        """
        import threading

        class L:
            def __init__(self):
                self._latest = None
                self._t = None
            def start(self):
                self._t = threading.Thread(target=self._run)
            def _run(self):
                self._latest = (0, {})
            def latest(self):
                if self._latest is not None:
                    return self._latest[1]
                return {}
        """,
    )
    assert any(f.rule == "THR001" for f in report.findings)


def test_multi_item_with_counts_as_nested_acquisition(tmp_path):
    """`with self.a, self.b:` is sugar for nesting (items acquire left to
    right) — an inversion against the one-line idiom must fire THR002
    exactly like the explicitly nested form."""
    report = _lint_source(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()
            def one(self):
                with self.a, self.b:
                    pass
            def two(self):
                with self.b:
                    with self.a:
                        pass
        """,
    )
    assert any(f.rule == "THR002" for f in report.findings)
    # consistent order across both forms stays clean
    clean = _lint_source(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()
            def one(self):
                with self.a, self.b:
                    pass
            def two(self):
                with self.a:
                    with self.b:
                        pass
        """,
    )
    assert not any(f.rule == "THR002" for f in clean.findings)


def test_suppression_reason_may_contain_parens():
    """Reasons naturally contain calls — 'len() is one GIL-atomic read'.
    The reason scan is paren-balanced, so neither a call nor a nested
    parenthetical truncates the audited justification, and the item
    separator still finds the next rule after the balanced close."""
    from dotaclient_tpu.analysis.core import Suppressions

    src = (
        "x = 1  # graftlint: disable="
        "THR001(len() is one GIL-atomic read), OBS001(see the (name) contract)\n"
    )
    sup = Suppressions(src)
    assert sup.missing_reason == []
    assert sup.covers("THR001", 1)
    assert sup.covers("OBS001", 1)
    assert sup._by_line[1]["THR001"] == "len() is one GIL-atomic read"
    assert sup._by_line[1]["OBS001"] == "see the (name) contract"


def test_suppression_spaced_equals_is_parsed():
    """`disable = RULE(reason)` — the formatter/habit spacing — must
    behave identically to the tight form. A silently-inert suppression
    (neither suppressing nor GRAFT000-reported) defeats the 'author
    learns the required syntax' contract: the default gate passes and
    the nightly --strict lane fails with no pointer at the comment."""
    from dotaclient_tpu.analysis.core import Suppressions

    sup = Suppressions("x = 1  # graftlint: disable = THR001(spaced form)\n")
    assert sup.covers("THR001", 1)
    bare = Suppressions("x = 1  # graftlint: disable = THR001\n")
    assert not bare.covers("THR001", 1)
    assert bare.missing_reason == [(1, "THR001")]


def test_positional_nonfunction_jit_arg_mints_no_region(tmp_path):
    """Only the FIRST positional of jit/shard_map/pmap is the wrapped
    callable — legacy `jax.jit(fn, device)` or positional-mesh shard_map
    must not turn a same-named function elsewhere in the file into a
    phantom jit region whose host I/O then false-fails the gate."""
    report = _lint_source(
        tmp_path,
        """
        import jax

        device = None

        def fn(x):
            return x

        jfn = jax.jit(fn, device)

        class Helper:
            def device(self):
                print("eager host-side helper, not a jit region")
                return 0
        """,
    )
    assert not any(f.rule.startswith("JAX") for f in report.findings)


def test_eager_call_to_raw_wrapped_fn_is_not_jax003(tmp_path):
    """The raw inner fn of `jfn = jax.jit(fn, ...)` stays callable eagerly
    (tests/debugging keep it around) — a direct call never enters jit, so
    an unhashable literal there is harmless and must not be flagged; the
    same literal through the jitted alias must still fire."""
    report = _lint_source(
        tmp_path,
        """
        import jax

        def fn(x, dims):
            return x

        jfn = jax.jit(fn, static_argnums=(1,))

        def eager_test_path(x):
            return fn(x, [1, 2])
        """,
    )
    assert not any(f.rule == "JAX003" for f in report.findings)
    flagged = _lint_source(
        tmp_path,
        """
        import jax

        def fn(x, dims):
            return x

        jfn = jax.jit(fn, static_argnums=(1,))

        def hot(x):
            return jfn(x, [1, 2])
        """,
    )
    assert any(f.rule == "JAX003" for f in flagged.findings)


def test_inline_suppression_with_reason_suppresses(tmp_path):
    report = _lint_source(
        tmp_path,
        """
        import threading

        class L:
            def __init__(self):
                self._pending = []
                self._t = None
            def start(self):
                self._t = threading.Thread(target=self._run)
            def _run(self):
                self._pending.append(1)
            def depth(self):
                return len(self._pending)  # graftlint: disable=THR001(len is one atomic read)
        """,
    )
    assert report.findings == []
    assert len(report.suppressed) == 1


# ------------------------------------------------- graftcheck lifecycle/wire


def _package_copy(tmp_path):
    """A linted-shape copy of the real tree (package + k8s; no scripts —
    the mutant tests target package files)."""
    shutil.copytree(
        os.path.join(REPO_ROOT, "dotaclient_tpu"), tmp_path / "dotaclient_tpu"
    )
    shutil.copytree(os.path.join(REPO_ROOT, "k8s"), tmp_path / "k8s")
    return tmp_path


def test_wire001_head_parity():
    """Acceptance bar: WIRE001 derives the SAME DTR layout from
    serialize.py (ast) and packer.cc (regex) on HEAD — header/trace
    sizes, wire codes, and all four canonical dtype-maps."""
    from dotaclient_tpu.analysis.lif_rules import (
        parse_packer_spec,
        parse_serialize_spec,
    )

    py, py_errs = parse_serialize_spec(
        os.path.join(REPO_ROOT, "dotaclient_tpu", "transport", "serialize.py")
    )
    cc, cc_errs = parse_packer_spec(
        os.path.join(REPO_ROOT, "dotaclient_tpu", "native", "packer.cc")
    )
    assert py_errs == [] and cc_errs == []
    assert py.diffs(cc) == []
    # the spec is substantive, not vacuously equal
    assert py.header_bytes == 21 and py.trace_ext_bytes == 16
    assert py.codes == {"f32": 0, "i32": 1, "u8": 2, "bf16": 3}
    assert len(py.maps[(False, False)]) == 16
    assert len(py.maps[(True, True)]) == 19


def test_early_lease_release_mutant_fails_lint(tmp_path):
    """Acceptance bar (the PR-11 regression, static half): re-introduce
    the early-lease-release bug into the REAL learner — release before
    the block_until_ready fence — and LIF001 catches it. (The dynamic
    half is schedcheck's ring model, tests/test_schedcheck.py.)"""
    root = _package_copy(tmp_path)
    lp = root / "dotaclient_tpu" / "runtime" / "learner.py"
    src = lp.read_text()
    mutant = src.replace(
        "                    jax.block_until_ready(batch_dev)\n"
        "                    lease.release()",
        "                    lease.release()",
    )
    assert mutant != src, "learner release site moved — update this pin"
    lp.write_text(mutant)
    report = lint_repo(str(root))
    lif = [f for f in report.findings if f.rule == "LIF001"]
    assert lif, "early-lease-release mutant not caught by LIF001"
    assert any("Learner._fetch_next" in f.context for f in lif)


def test_packer_layout_drift_mutant_fails_lint(tmp_path):
    """A dtype-map loop-boundary edit in the REAL packer.cc that
    serialize.py does not mirror fails WIRE001."""
    root = _package_copy(tmp_path)
    pp = root / "dotaclient_tpu" / "native" / "packer.cc"
    src = pp.read_text()
    mutant = src.replace(
        "for (int64_t i = 6; i < 10; ++i)", "for (int64_t i = 6; i < 9; ++i)"
    ).replace(
        "for (int64_t i = 10; i < n_map; ++i)",
        "for (int64_t i = 9; i < n_map; ++i)",
    )
    assert mutant != src, "packer.cc validation loops moved — update this pin"
    pp.write_text(mutant)
    report = lint_repo(str(root))
    wire = [f for f in report.findings if f.rule == "WIRE001"]
    assert wire and all("dtype-map" in f.message for f in wire)


def test_packer_unparseable_layout_is_itself_a_finding(tmp_path):
    """WIRE001 extraction failing (a layout edit that breaks the
    structured regexes) is a loud finding, never a silent skip — the
    MIGRATION contract that packer.cc edits keep the spec extractable."""
    root = _package_copy(tmp_path)
    pp = root / "dotaclient_tpu" / "native" / "packer.cc"
    pp.write_text(pp.read_text().replace("constexpr int64_t kHeaderBytes", "static int64_t header_bytes"))
    report = lint_repo(str(root))
    wire = [f for f in report.findings if f.rule == "WIRE001"]
    assert wire and any("extraction failed" in f.message for f in wire)


def test_wire_pair_half_missing_is_loud(tmp_path):
    """Renaming/moving ONE side of the serialize.py↔packer.cc pair must
    not make WIRE001 vanish silently — half a pair is a finding; only a
    corpus with NEITHER file (no wire layer at all) skips."""
    root = _package_copy(tmp_path)
    os.remove(root / "dotaclient_tpu" / "native" / "packer.cc")
    report = lint_repo(str(root))
    wire = [f for f in report.findings if f.rule == "WIRE001"]
    assert wire and any("lost half its pair" in f.message for f in wire)


def test_serialize_alias_refactor_is_loud_not_dead(tmp_path):
    """A _canonical_codes refactor through a local alias defeats the
    list-algebra extraction — that must surface as an extraction-failed
    FINDING, never an exception that kills the lint run and loses every
    other rule's findings."""
    root = _package_copy(tmp_path)
    sp = root / "dotaclient_tpu" / "transport" / "serialize.py"
    src = sp.read_text()
    mutant = src.replace(
        "codes = [obs_code] * 3 + [_WIRE_U8] * 3 + [_WIRE_I32] * 4 "
        "+ [_WIRE_F32] * 6",
        "f = _WIRE_F32\n    codes = [obs_code] * 3 + [_WIRE_U8] * 3 "
        "+ [_WIRE_I32] * 4 + [f] * 6",
    )
    assert mutant != src, "_canonical_codes body moved — update this pin"
    sp.write_text(mutant)
    report = lint_repo(str(root))  # must not raise
    wire = [f for f in report.findings if f.rule == "WIRE001"]
    assert wire and any("extraction failed" in f.message for f in wire)


def test_scripts_flag_drift_mutant_fails_lint(tmp_path):
    """The OBS002 scripts pass on a REAL-shaped tree: a driver spawning
    a known binary with an unknown flag fails the lint."""
    root = _package_copy(tmp_path)
    scripts = root / "scripts"
    scripts.mkdir()
    (scripts / "bad_driver.py").write_text(
        "import subprocess, sys\n"
        "def spawn():\n"
        "    subprocess.Popen([sys.executable, '-m',\n"
        "                      'dotaclient_tpu.serve.server',\n"
        "                      '--serve.port', '0',\n"
        "                      '--serve.bogus_knob', '1'])\n"
    )
    report = lint_repo(str(root))
    obs = [f for f in report.findings if f.rule == "OBS002"]
    assert any("--serve.bogus_knob" in f.message for f in obs)


# ------------------------------------------------------------- nightly lane


@pytest.mark.nightly
@pytest.mark.slow
def test_lint_strict_nightly():
    """The nightly wrapper: scripts/lint_graft.py --strict must pass on
    the checked-in tree (warnings included)."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "lint_graft.py"),
            "--strict",
            "--json",
        ],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["ok"] and payload["files_scanned"] > 50
