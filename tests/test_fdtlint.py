"""fdtlint tier-1 surface.

Three contracts, per ISSUE 2's acceptance criteria:

  1. the repo itself is lint-clean (the checkers gate regressions, so
     the baseline must hold at zero findings);
  2. the ABI checker verifiably covers every ctypes binding module —
     coverage is asserted, not assumed, because a checker that scans
     nothing "passes" forever;
  3. every known-bad corpus fixture trips its rule and every known-good
     fixture scans clean, so the rules cannot silently rot.

Everything here is AST/regex level: no native build, no jax import.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from firedancer_tpu.analysis import engine

REPO = Path(__file__).resolve().parent.parent
CORPUS = REPO / "tests" / "fixtures" / "lint_corpus"

#: the ctypes binding modules the ABI checker must demonstrably scan:
#: the six named by ISSUE 2 plus the fdt_bank executor driver (ISSUE 9)
SIX_BINDING_MODULES = {
    "firedancer_tpu/tango/rings.py",
    "firedancer_tpu/ops/ed25519/verify.py",
    "firedancer_tpu/ops/ed25519/sign.py",
    "firedancer_tpu/tiles/wire.py",
    "firedancer_tpu/tiles/bench.py",
    "firedancer_tpu/flamenco/runtime.py",
    # block-egress call-site binders (ISSUE 12)
    "firedancer_tpu/tiles/net.py",
    "firedancer_tpu/tiles/quic.py",
}

#: known-bad fixture -> the rule it must trip
BAD_FIXTURES = {
    "ring_bad_foreign_fseq.py": "ring-fseq-owner",
    "ring_bad_overrun_discard.py": "ring-overrun",
    "ring_bad_overrun_unused.py": "ring-overrun",
    "ring_bad_write_after_publish.py": "ring-publish-order",
    "ring_bad_publish_no_credit.py": "ring-credit",
    "ring_bad_unhooked_ringop.py": "ring-mc-hook",
    "ring_bad_device_dispatch.py": "device-dispatch",
    "ring_bad_stem_handler.py": "stem-native-handler",
    "ring_bad_hot_clock.py": "hot-path-clock",
    "ring_bad_admission_clock.py": "hot-path-clock",
    "ring_bad_skip_handshake.py": "ring-handshake-rebind",
    "proc_bad_unsafe_tile.py": "proc-safe-tile",
    "purity_bad_host_sync.py": "purity-host-sync",
    "purity_bad_float.py": "purity-float",
    "purity_bad_branch.py": "purity-untraced-branch",
    "metrics_bad_undeclared.py": "metrics-schema",
    # C-side rules: stem-emit-only (ISSUE 15) + the fdtshm
    # shared-memory contract (ISSUE 18)
    "native_bad_raw_publish.c": "stem-emit-only",
    "shm_bad_missing_release.c": "shm-publish-release",
    "shm_bad_second_writer.c": "shm-single-writer",
    "shm_bad_stale_credit.c": "shm-stale-credit",
    "shm_bad_journal_mutate.c": "shm-journal-arm",
    "shm_bad_epoch_skip.c": "shm-epoch-check",
}

ABI_BAD_RULES = {
    "abi-arity",
    "abi-argtype",
    "abi-restype",
    "abi-unknown-symbol",
    "abi-unbound-export",
    "abi-call-arity",
    "abi-call-unknown",
}


@pytest.fixture(scope="module")
def repo_report():
    return engine.run_repo(REPO)


# ---------------------------------------------------------------------------
# 1. the repo ships lint-clean


def test_repo_is_lint_clean(repo_report):
    assert repo_report.findings == [], "\n" + "\n".join(
        str(f) for f in repo_report.findings
    )


# ---------------------------------------------------------------------------
# 2. coverage is real


def test_abi_covers_all_six_binding_modules(repo_report):
    cov = repo_report.coverage["abi"]
    missing = SIX_BINDING_MODULES - set(cov["modules"])
    assert not missing, f"ABI checker skipped binding modules: {missing}"


def test_abi_coverage_is_substantive(repo_report):
    cov = repo_report.coverage["abi"]
    assert cov["tables"] >= 1
    # 53 pre-fdt_bank symbols + 8 fdt_bank_* batch-executor exports + 3
    # fdt_stem exports (cfg_words / run / bank_pipeline, ISSUE 10) + the
    # fdt_pack_sched after-credit scheduler (ISSUE 11) + the 14
    # block-egress exports (4 fdt_sha256_*, 2 fdt_poh_*, 3
    # fdt_shred_*, 3 fdt_net_*, 2 fdt_stem_out_* — ISSUE 12) + the 8
    # in-burst trace exports (7 fdt_trace_* + fdt_stem_out_emit_at —
    # ISSUE 15)
    assert len(cov["table_symbols"]) >= 86, cov["table_symbols"]
    assert cov["call_sites"] >= 58  # rings.py methods + the direct binders
    # the native exported surface and the ctypes tables are in bijection:
    # no unbound exports, no phantom bindings
    assert set(cov["c_symbols"]) == set(cov["table_symbols"])


def test_ring_and_purity_coverage(repo_report):
    cov = repo_report.coverage
    ring = set(cov["ring_files"])
    assert "firedancer_tpu/disco/mux.py" in ring
    assert "firedancer_tpu/tiles/verify.py" in ring
    assert "firedancer_tpu/tiles/shred.py" in ring
    assert len(ring) >= 20
    # the marked kernel-layer surface: verify (3), the Pallas kernel,
    # sha512, the corpus signer, pack_select
    assert cov["hot_functions"] >= 7


def test_mc_hook_coverage(repo_report):
    """tango/rings.py is scanned for ring-mc-hook and its guarded
    shared-memory op surface cannot silently shrink: every MCache/DCache/
    FSeq runtime method plus cr_avail must route through the fdtmc hook."""
    cov = repo_report.coverage
    assert "firedancer_tpu/tango/rings.py" in set(cov["ring_files"])
    # +1: Stem.run (the native stem entry point is guarded too — under
    # fdtmc it must never run)
    assert cov["mc_hook_fns"] >= 14, cov["mc_hook_fns"]


def test_device_dispatch_fixture_controls_are_clean():
    """The rule flags every direct device call in the eager tile's hook
    bodies and NONE in the two controls (pool-routed hooks; a Worker/
    Pool-owned method, even hook-named)."""
    rep = engine.run_paths([CORPUS / "ring_bad_device_dispatch.py"])
    hits = [f for f in rep.findings if f.rule == "device-dispatch"]
    assert len(hits) == 4, hits  # the four BAD lines in EagerVerifyTile
    assert all(f.line < 30 for f in hits), hits  # controls stay clean


def test_hot_clock_fixture_controls_are_clean():
    """The rule flags every bare time.* clock read in the impatient
    tile's hook bodies and NONE in the controls (sanctioned now_ts /
    tempo.tickcount helpers; a Worker/Pool-owned hook-named method)."""
    rep = engine.run_paths([CORPUS / "ring_bad_hot_clock.py"])
    hits = [f for f in rep.findings if f.rule == "hot-path-clock"]
    assert len(hits) == 4, hits  # the four BAD reads in ImpatientTile
    assert all(f.line < 32 for f in hits), hits  # controls stay clean


def test_admission_clock_fixture_controls_are_clean():
    """The ISSUE 13 coverage extension: the rule flags every bare
    time.* read in admission-policy class methods (TokenBucket /
    Admission tags) and NONE in the controls (caller-supplied `now`,
    ordinary host-side functions)."""
    rep = engine.run_paths([CORPUS / "ring_bad_admission_clock.py"])
    hits = [f for f in rep.findings if f.rule == "hot-path-clock"]
    assert len(hits) == 3, hits  # bucket.take + admit_handshake + sweep
    assert all(f.line < 49 for f in hits), hits  # controls stay clean
    assert all("admission-policy" in f.msg for f in hits), hits


def test_proc_safe_fixture_controls_are_clean():
    """The rule flags the four unpicklable ctor captures + the module-
    state mutation in UnsafeTile, and NONE of the controls (on_boot
    resources, proc_safe=False observers, Worker classes, read-only
    module constants)."""
    rep = engine.run_paths([CORPUS / "proc_bad_unsafe_tile.py"])
    hits = [f for f in rep.findings if f.rule == "proc-safe-tile"]
    assert len(hits) == 5, hits
    assert all(f.line < 30 for f in hits), hits  # controls stay clean


def test_metrics_schema_fixture_controls_are_clean():
    """The rule flags exactly the three undeclared literal writes; the
    controls (declared names, base schema, dynamic per-link/per-device
    families, non-literal names, dynamic-schema classes) stay clean."""
    rep = engine.run_paths([CORPUS / "metrics_bad_undeclared.py"])
    hits = [f for f in rep.findings if f.rule == "metrics-schema"]
    assert len(hits) == 3, hits
    assert {"typo_txns", "gauge_typo", "latency_su"} == {
        f.msg.split("'")[1] for f in hits
    }


def test_metrics_schema_base_mirror_cannot_drift():
    """ringlint mirrors the base tile schema literally (it is stdlib-
    only and cannot import disco.metrics, which pulls numpy); this pins
    the mirror to the real schema so a base rename fails loudly here
    instead of silently un-covering the rule."""
    from firedancer_tpu.analysis import ringlint
    from firedancer_tpu.disco.metrics import DEVICE_METRICS, MetricsSchema

    assert ringlint.BASE_SCHEMA_COUNTERS == MetricsSchema.BASE_COUNTERS
    assert ringlint.BASE_SCHEMA_HISTS == MetricsSchema.BASE_HISTS
    assert ringlint.DEVICE_METRIC_NAMES == DEVICE_METRICS
    # the device family exempts EXACTLY dev{i}_{metric} — typos near it
    # must still trip the rule
    assert ringlint._is_dynamic_metric("dev3_landed")
    assert not ringlint._is_dynamic_metric("devcie0_landed")
    assert not ringlint._is_dynamic_metric("dev_resets")
    assert not ringlint._is_dynamic_metric("dev0_typo")


def test_unhooked_fixture_guarded_control_is_clean():
    """The guarded method in the ring-mc-hook fixture must NOT trip the
    rule (the rule keys on missing guards, not on native calls per se)."""
    rep = engine.run_paths([CORPUS / "ring_bad_unhooked_ringop.py"])
    lines = [f.line for f in rep.findings if f.rule == "ring-mc-hook"]
    assert len(lines) == 1  # only the unguarded call site


# ---------------------------------------------------------------------------
# 3. the corpus pins every rule


@pytest.mark.parametrize("name,rule", sorted(BAD_FIXTURES.items()))
def test_bad_fixture_trips_its_rule(name, rule):
    rep = engine.run_paths([CORPUS / name])
    rules = {f.rule for f in rep.findings}
    assert rule in rules, f"{name}: expected {rule}, got {sorted(rules)}"


def test_abi_bad_fixture_trips_every_abi_rule():
    rep = engine.run_paths([CORPUS / "abi_bad"])
    rules = {f.rule for f in rep.findings}
    missing = ABI_BAD_RULES - rules
    assert not missing, f"abi_bad fixture no longer trips: {missing}"
    # negative control: the one clean table entry stays clean
    assert not any(
        "fdt_mini_ok" in f.msg and f.rule not in ("abi-call-arity",)
        for f in rep.findings
    )


def test_stem_handler_fixture_controls_are_clean():
    """The rule flags every ring/metric mutation in the eager tile's
    native_handler (including the ready-closure drain) and NONE in the
    descriptor-only control."""
    rep = engine.run_paths([CORPUS / "ring_bad_stem_handler.py"])
    hits = [f for f in rep.findings if f.rule == "stem-native-handler"]
    assert len(hits) >= 3, [str(f) for f in rep.findings]
    assert not any("DescriptorOnly" in f.msg for f in hits)
    bad_lines = {f.line for f in hits}
    src = (CORPUS / "ring_bad_stem_handler.py").read_text().splitlines()
    # every hit lands inside the EagerStemTile class body
    eager_end = next(
        i for i, ln in enumerate(src, 1) if "DescriptorOnly" in ln
    )
    assert all(ln < eager_end for ln in bad_lines), sorted(bad_lines)


def test_stem_emit_only_fixture_flags_raw_publishes():
    """ISSUE 15 satellite: the C-side stem-emit-only rule flags raw
    fdt_mcache_publish(_batch) calls in native handler sources — those
    bypass per-frag tspub stamping and native span emission — naming
    the enclosing function; pragma'd sites and comment mentions are
    clean."""
    rep = engine.run_paths([CORPUS / "native_bad_raw_publish.c"])
    hits = [f for f in rep.findings if f.rule == "stem-emit-only"]
    assert len(hits) == 2, [str(f) for f in rep.findings]
    assert all("h_bad_handler" in f.msg for f in hits)
    assert not any("h_pragma_ok" in f.msg for f in hits)


def test_stem_emit_only_repo_surface_is_covered(repo_report):
    """Every tango/native .c joins the scan (fdt_tango.c is listed but
    exempt inside the checker), and the live sources are clean — every
    native publish routes through the stem emit bodies."""
    cov = repo_report.coverage
    native = set(cov.get("native_c_files", ()))
    for must in (
        "firedancer_tpu/tango/native/fdt_stem.c",
        "firedancer_tpu/tango/native/fdt_net.c",
        "firedancer_tpu/tango/native/fdt_pack.c",
        "firedancer_tpu/tango/native/fdt_trace.c",
    ):
        assert must in native, native
    assert not [
        f for f in repo_report.findings if f.rule == "stem-emit-only"
    ]


def test_good_fixtures_scan_clean():
    rep = engine.run_paths(
        [
            CORPUS / "ring_good.py",
            CORPUS / "purity_good.py",
            CORPUS / "abi_good",
            CORPUS / "shm_good.c",
        ]
    )
    assert rep.findings == [], "\n" + "\n".join(str(f) for f in rep.findings)


def test_every_bad_fixture_on_disk_is_asserted():
    on_disk = {
        p.name
        for pat in ("*_bad_*.py", "*_bad_*.c")
        for p in CORPUS.glob(pat)
    }
    assert on_disk == set(BAD_FIXTURES), (
        "corpus and BAD_FIXTURES table drifted — every known-bad snippet "
        "must be pinned to the rule it exercises"
    )


# ---------------------------------------------------------------------------
# CLI contract (scripts/fdtlint.py): exit 0 on the repo, non-zero on every
# known-bad fixture, --json machine readable


def _cli(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "fdtlint.py"), *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_repo_pass_is_clean_and_json_parses():
    r = _cli("--json")
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["ok"] is True and doc["findings"] == []
    assert set(doc["coverage"]["abi"]["modules"]) >= SIX_BINDING_MODULES


def test_cli_nonzero_on_every_bad_fixture():
    targets = sorted(BAD_FIXTURES) + ["abi_bad"]
    for name in targets:
        r = _cli("--json", str(CORPUS / name))
        assert r.returncode == 1, f"{name}: rc={r.returncode}\n{r.stdout}{r.stderr}"
        doc = json.loads(r.stdout)
        assert doc["ok"] is False and doc["findings"]


# ---------------------------------------------------------------------------
# baseline files: accepted-findings suppression without inline pragmas


def test_baseline_roundtrip_suppresses_and_reports_stale(tmp_path):
    from firedancer_tpu.analysis import findings as F

    target = CORPUS / "ring_bad_overrun_discard.py"
    rep = engine.run_paths([target])
    assert rep.findings

    base_file = tmp_path / "baseline.json"
    F.write_baseline(rep.findings, str(base_file))
    base = F.load_baseline(str(base_file))
    kept, suppressed, stale = F.apply_baseline(rep.findings, base)
    assert kept == [] and suppressed == len(rep.findings) and stale == []

    # a baseline from another file suppresses nothing and is ALL stale
    other = engine.run_paths([CORPUS / "ring_bad_foreign_fseq.py"]).findings
    kept, suppressed, stale = F.apply_baseline(other, base)
    assert kept == other and suppressed == 0 and len(stale) == len(base)


def test_baseline_matches_across_invocation_styles(tmp_path):
    """A baseline written from one invocation style (relative path) must
    suppress the same findings reported under another (absolute path):
    keys normalize to repo-relative paths."""
    from firedancer_tpu.analysis import findings as F

    import os

    target = CORPUS / "ring_bad_overrun_discard.py"
    abs_findings = engine.run_paths([str(target)]).findings
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        rel_findings = engine.run_paths(
            [str(target.relative_to(REPO))]
        ).findings
    finally:
        os.chdir(cwd)
    assert abs_findings and rel_findings
    base_file = tmp_path / "b.json"
    F.write_baseline(abs_findings, str(base_file))
    kept, suppressed, stale = F.apply_baseline(
        rel_findings, F.load_baseline(str(base_file))
    )
    assert kept == [] and suppressed == len(rel_findings) and stale == []


def test_cli_baseline_flags(tmp_path):
    base_file = tmp_path / "base.json"
    target = str(CORPUS / "ring_bad_overrun_discard.py")
    r = _cli("--write-baseline", str(base_file), target)
    assert r.returncode == 0, r.stdout + r.stderr
    assert base_file.exists()
    # with the baseline, the known-bad fixture scans clean (exit 0)
    r = _cli("--json", "--baseline", str(base_file), target)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["ok"] is True
    assert doc["coverage"]["baseline"]["suppressed"] >= 1
    # against a different file the baseline suppresses nothing (exit 1)
    # and its now-stale entries are reported on stderr
    other = str(CORPUS / "ring_bad_foreign_fseq.py")
    r = _cli("--json", "--baseline", str(base_file), other)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "stale baseline entry" in r.stderr
    # malformed baseline -> usage error contract
    bad = tmp_path / "bad.json"
    bad.write_text('{"not": "a list"}')
    r = _cli("--baseline", str(bad), target)
    assert r.returncode == 2


# ---------------------------------------------------------------------------
# satellite: tango.rings._bind names the missing symbol on ABI drift


def test_rings_bind_error_names_missing_symbol():
    # AST-free import: rings pulls in the native build, which tier-1
    # already pays for in test_tango — reuse it here
    from firedancer_tpu.tango import rings

    class _HollowLib:
        def __getattr__(self, name):
            raise AttributeError(name)

    with pytest.raises(RuntimeError, match=r"fdt_mcache_poll.*drifted"):
        rings._bind(_HollowLib(), {"fdt_mcache_poll": (None, [])})


def test_rings_bind_applies_table():
    from firedancer_tpu.tango import rings

    class _Fn:
        restype = None
        argtypes = None

    class _Lib:
        fdt_x = _Fn()

    lib = _Lib()
    rings._bind(lib, {"fdt_x": (int, [float])})
    assert lib.fdt_x.restype is int and lib.fdt_x.argtypes == [float]
