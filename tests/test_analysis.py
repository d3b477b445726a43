"""The static-analysis gate: the multi-pass framework (registry,
suppression pragmas, reporters) plus every checker's fixture
round-trip — hot-path sync/allocation rules, lock discipline
(LD1..LD4), dispatch discipline (DD1..DD5), and lifecycle discipline
(LC1..LC4). The whole suite must run clean over the real serving
stack (suppressions honored), and each checker must actually catch
each violation class. Stdlib-only: this file never imports jax (the
fixtures mentioning jax are PARSED, never imported)."""

import json
import pathlib
import re
import subprocess
import sys
import time

import pytest

from cloud_server_tpu.analysis import (HOT_PATHS, Finding,
                                       apply_pragmas, check_hot_paths,
                                       check_source, collect_pragmas,
                                       dispatch, lifecycle, locks,
                                       registered_passes, report_json,
                                       run_analysis)
from cloud_server_tpu.analysis.framework import (pragma_lines,
                                                 report_sarif)

_HERE = pathlib.Path(__file__).resolve().parent
_FIXTURES = _HERE / "analysis_fixtures"


def test_registered_hot_paths_are_clean():
    findings = check_hot_paths(str(_HERE.parent))
    assert not findings, "\n".join(str(f) for f in findings)


def test_registry_covers_qos_admission_policy():
    """The per-iteration QoS entry points must stay registered — the
    lint is the standing guarantee that fair-share admission never
    reintroduces per-iteration syncs or device allocations."""
    quals = set(HOT_PATHS["cloud_server_tpu/inference/qos.py"])
    for needed in ("TenantRegistry.next_admission_index",
                   "TenantRegistry.order_jobs",
                   "TenantRegistry.charge_prefill",
                   "TenantRegistry.charge_generated",
                   "TenantRegistry.victim_rank",
                   "TokenBucket.try_consume"):
        assert needed in quals, f"{needed} dropped from HOT_PATHS"


def test_registry_covers_tracing_and_slo():
    """The span-record path (request_trace.py) and the SLO observe
    path (slo.py) ride inside the scheduler iteration alongside the
    QoS policy — they must stay on the scan roster."""
    trace_quals = set(
        HOT_PATHS["cloud_server_tpu/inference/request_trace.py"])
    for needed in ("RequestTrace.add_span", "TraceRecorder.begin",
                   "TraceRecorder.finish"):
        assert needed in trace_quals, f"{needed} dropped from HOT_PATHS"
    slo_quals = set(HOT_PATHS["cloud_server_tpu/inference/slo.py"])
    for needed in ("SLOTracker.observe", "_RollingCounts.observe"):
        assert needed in slo_quals, f"{needed} dropped from HOT_PATHS"


def test_checker_flags_bad_trace_and_slo_paths():
    """Fixture round-trip for the NEW roster entries' violation
    shapes: wall-clock span stamps, per-span numpy buffers, logging,
    I/O and sleeps inside observe — each must fire; the pure
    passed-timestamp shape the real modules use must not."""
    src = (_FIXTURES / "hot_path_trace_bad.py").read_text()
    cases = {
        "BadRecorder.add_span_wall_clock": "time.time",
        "BadRecorder.add_span_numpy": "numpy",
        "BadRecorder.add_span_logged": "logging",
        "BadSLO.observe_io": "I/O",
        "BadSLO.observe_sleepy": "sleep",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_trace_bad.py", src, (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    assert not check_source("hot_path_trace_bad.py", src,
                            ("BadSLO.observe_fine",))


def test_registry_covers_spec_control():
    """The adaptive-speculation controller runs inside the scheduler
    iteration (planning per dispatch, feedback per committed round) —
    its hot surface must stay on the scan roster."""
    quals = set(HOT_PATHS["cloud_server_tpu/inference/spec_control.py"])
    for needed in ("SpecController.draft_len",
                   "SpecController.observe",
                   "SpecController.on_plain_dispatch",
                   "SpecController.draft_lengths"):
        assert needed in quals, f"{needed} dropped from HOT_PATHS"
    qos_quals = set(HOT_PATHS["cloud_server_tpu/inference/qos.py"])
    assert "TenantRegistry.charge_speculation" in qos_quals


def test_checker_flags_bad_spec_control_paths():
    """Fixture round-trip for the spec-control roster: device work in
    dispatch planning, numpy buffers per observed round, wall-clock
    rate decay, logging and I/O — each violation class must fire."""
    src = (_FIXTURES / "hot_path_spec_bad.py").read_text()
    cases = {
        "BadSpecController.draft_len_device": "device",
        "BadSpecController.observe_numpy": "numpy",
        "BadSpecController.accept_rate_wall_clock": "time.time",
        "BadSpecController.observe_logged": "logging",
        "BadSpecController.on_plain_dispatch_io": "I/O",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_spec_bad.py", src, (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"


def test_registry_covers_iteration_profile():
    """The iteration-phase profiler's record path runs at every phase
    boundary of every scheduler iteration — the tightest loop on the
    roster — and the module must stay jax-free (it is consulted from
    the server's step loop)."""
    quals = set(
        HOT_PATHS["cloud_server_tpu/inference/iteration_profile.py"])
    for needed in ("IterationProfiler.begin", "IterationProfiler.enter",
                   "IterationProfiler.end", "IterationProfiler.close",
                   "IterationProfiler.phases_ms", "derive_gap_fields"):
        assert needed in quals, f"{needed} dropped from HOT_PATHS"
    assert ("cloud_server_tpu/inference/iteration_profile.py"
            in dispatch.HOST_POLICY_MODULES), \
        "iteration_profile.py dropped from the DD3 host-policy roster"


def test_checker_flags_bad_profile_paths():
    """Fixture round-trip proving the checker is LIVE on the new
    module's violation shapes: wall-clock phase stamps, numpy buffers
    per mark, a blocking sync 'for honest device timing', logging and
    I/O per iteration — each must fire; the pure passed-timestamp
    shape the real profiler uses must not."""
    src = (_FIXTURES / "hot_path_profile_bad.py").read_text()
    cases = {
        "BadProfiler.mark_wall_clock": "time.time",
        "BadProfiler.mark_numpy": "numpy",
        "BadProfiler.mark_synced": "sync",
        "BadProfiler.finish_logged": "logging",
        "BadProfiler.finish_io": "I/O",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_profile_bad.py", src, (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    assert not check_source("hot_path_profile_bad.py", src,
                            ("BadProfiler.mark_fine",))


def test_registry_covers_cache_telemetry():
    """The cache-telemetry record hooks run inside the allocator's
    lookup/alloc/release/evict — i.e. inside every scheduler iteration
    that moves pages — and the module must stay jax-free (DD3) since
    both the allocator and the router's fleet merge consult it."""
    quals = set(
        HOT_PATHS["cloud_server_tpu/inference/cache_telemetry.py"])
    for needed in ("CacheTelemetry.record_walk",
                   "CacheTelemetry.record_evict",
                   "CacheTelemetry.record_saved",
                   "CacheTelemetry._compact"):
        assert needed in quals, f"{needed} dropped from HOT_PATHS"
    assert ("cloud_server_tpu/inference/cache_telemetry.py"
            in dispatch.HOST_POLICY_MODULES), \
        "cache_telemetry.py dropped from the DD3 host-policy roster"
    router_quals = set(HOT_PATHS["cloud_server_tpu/inference/router.py"])
    assert "ReplicatedRouter.cache_stats" in router_quals


def test_checker_flags_bad_cache_paths():
    """Fixture round-trip proving the checker is LIVE on the cache
    module's violation shapes: wall-clock eviction stamps, numpy
    buffers per walk, a blocking sync for pool occupancy, logging and
    I/O per eviction — each must fire; the dict-arithmetic shape the
    real telemetry uses must not."""
    src = (_FIXTURES / "hot_path_cache_bad.py").read_text()
    cases = {
        "BadCacheTelemetry.record_evict_wall_clock": "time.time",
        "BadCacheTelemetry.record_walk_numpy": "numpy",
        "BadCacheTelemetry.record_walk_synced": "sync",
        "BadCacheTelemetry.record_evict_logged": "logging",
        "BadCacheTelemetry.record_evict_io": "I/O",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_cache_bad.py", src, (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    assert not check_source("hot_path_cache_bad.py", src,
                            ("BadCacheTelemetry.record_walk_fine",))


def test_registry_covers_faults():
    """The failure-domain layer's fire/check run per guarded site hit
    on the scheduler iteration and submit paths, and the brownout
    detector gates every submit — rostered like cache_telemetry.py on
    all three passes (hot-path here; DD3 host-policy; lock-discipline
    via LOCK_ROSTER)."""
    from cloud_server_tpu.analysis import locks
    quals = set(HOT_PATHS["cloud_server_tpu/inference/faults.py"])
    for needed in ("FaultPlan.fire", "FaultPlan.check",
                   "OverloadDetector.observe", "OverloadDetector.shed",
                   "OverloadDetector.retry_hint"):
        assert needed in quals, f"{needed} dropped from HOT_PATHS"
    assert ("cloud_server_tpu/inference/faults.py"
            in dispatch.HOST_POLICY_MODULES), \
        "faults.py dropped from the DD3 host-policy roster"
    assert ("cloud_server_tpu/inference/faults.py"
            in locks.LOCK_ROSTER), \
        "faults.py dropped from the lock-discipline roster"
    # the per-submit deadline default lookup rode onto the qos roster
    assert ("TenantRegistry.default_deadline"
            in HOT_PATHS["cloud_server_tpu/inference/qos.py"])


def test_checker_flags_bad_fault_paths():
    """Fixture round-trip proving the checker is LIVE on the new
    module's violation shapes: a sleep inside fire() (blocking belongs
    only in the unrostered maybe_stall/maybe_wedge), wall-clock
    overload stamps, numpy signal buffers, a blocking sync to grade
    overload, logging/IO on the shed path — each must fire; the
    dict-lookup shed shape the real detector uses must not."""
    src = (_FIXTURES / "hot_path_faults_bad.py").read_text()
    cases = {
        "BadFaultPlan.fire_sleeps": "sleep",
        "BadFaultPlan.fire_logged": "logging",
        "BadFaultPlan.check_io": "I/O",
        "BadOverloadDetector.observe_wall_clock": "time.time",
        "BadOverloadDetector.observe_numpy": "numpy",
        "BadOverloadDetector.level_synced": "sync",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_faults_bad.py", src, (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    assert not check_source("hot_path_faults_bad.py", src,
                            ("BadOverloadDetector.shed_fine",))


def test_registry_covers_anomaly():
    """The anomaly watchdog rides all three passes: observe_* run
    once per busy iteration / per completion (hot-path), the module
    is stdlib-only host policy (DD3), and its leaf lock is
    lock-discipline audited. The tail-retention verdict helpers ride
    the existing request_trace/slo rosters."""
    from cloud_server_tpu.analysis import locks
    quals = set(HOT_PATHS["cloud_server_tpu/inference/anomaly.py"])
    for needed in ("AnomalyWatchdog.observe_iteration",
                   "AnomalyWatchdog.observe_request",
                   "AnomalyWatchdog.active_count",
                   "AnomalyWatchdog._update_rule",
                   "AnomalyWatchdog._shift"):
        assert needed in quals, f"{needed} dropped from HOT_PATHS"
    assert ("cloud_server_tpu/inference/anomaly.py"
            in dispatch.HOST_POLICY_MODULES), \
        "anomaly.py dropped from the DD3 host-policy roster"
    assert ("cloud_server_tpu/inference/anomaly.py"
            in locks.LOCK_ROSTER), \
        "anomaly.py dropped from the lock-discipline roster"
    # the tail-retention verdict + SLO target check ride the existing
    # rosters of the modules they live in
    assert ("TraceRecorder._tail_reason"
            in HOT_PATHS["cloud_server_tpu/inference/request_trace.py"])
    assert ("SLOTracker.exceeds_target"
            in HOT_PATHS["cloud_server_tpu/inference/slo.py"])


def test_checker_flags_bad_anomaly_paths():
    """Fixture round-trip proving the checker is LIVE on the new
    module's violation shapes: wall-clock window stamps, numpy signal
    buffers, logging the fired rule from the scheduler thread, disk
    IO for the bundle on the activation edge, a blocking sync to
    grade a latency signal, sleeping out the hysteresis hold — each
    must fire; the dict/float window-update shape the real watchdog
    uses must not."""
    src = (_FIXTURES / "hot_path_anomaly_bad.py").read_text()
    cases = {
        "BadWatchdog.observe_wall_clock": "time.time",
        "BadWatchdog.observe_numpy": "numpy",
        "BadWatchdog.fire_logged": "logging",
        "BadWatchdog.bundle_io": "I/O",
        "BadWatchdog.shift_synced": "sync",
        "BadWatchdog.hold_sleeps": "sleep",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_anomaly_bad.py", src, (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    assert not check_source("hot_path_anomaly_bad.py", src,
                            ("BadWatchdog.update_fine",))


def test_registry_covers_migration():
    """Live migration rides all three passes: the ledger's record
    hooks run while a scheduler's step lock is held (hot-path), the
    module itself is host policy (DD3), the export's KV gather is a
    sanctioned sync with the whole export/import path on the DD2
    scheduler roster, and the ledger's leaf lock is lock-discipline
    audited."""
    from cloud_server_tpu.analysis import locks
    quals = set(HOT_PATHS["cloud_server_tpu/inference/migration.py"])
    for needed in ("MigrationLedger.record_export_done",
                   "MigrationLedger.record_import_done",
                   "MigrationLedger.drain_flight_deltas",
                   "MigrationSnapshot.remaining_new_tokens"):
        assert needed in quals, f"{needed} dropped from HOT_PATHS"
    assert ("cloud_server_tpu/inference/migration.py"
            in dispatch.HOST_POLICY_MODULES), \
        "migration.py dropped from the DD3 host-policy roster"
    assert ("cloud_server_tpu/inference/migration.py"
            in locks.LOCK_ROSTER), \
        "migration.py dropped from the lock-discipline roster"
    paged = "cloud_server_tpu/inference/paged_server.py"
    assert ("PagedInferenceServer._export_request_locked"
            in dispatch.SANCTIONED_SYNCS[paged]), \
        "the migration export's sync lost its DD2 sanction"
    loop = set(dispatch.SCHEDULER_LOOPS[paged])
    for needed in ("PagedInferenceServer.migrate_export",
                   "PagedInferenceServer.migrate_import",
                   "PagedInferenceServer._import_pages",
                   "PagedInferenceServer._evacuate"):
        assert needed in loop, f"{needed} dropped from the DD2 roster"


def test_checker_flags_bad_migration_paths():
    """Fixture round-trip proving the checker is LIVE on the new
    module's violation shapes: logging/IO from record hooks that run
    under a scheduler's step lock, wall-clock flight-delta stamps,
    numpy counter buffers, a second sync after the export's sanctioned
    one, a pacing sleep — each must fire; the int-add ledger shape the
    real module uses must not."""
    src = (_FIXTURES / "hot_path_migration_bad.py").read_text()
    cases = {
        "BadMigrationLedger.record_export_done_logged": "logging",
        "BadMigrationLedger.record_import_done_io": "I/O",
        "BadMigrationLedger.drain_flight_wall_clock": "time.time",
        "BadMigrationLedger.stats_numpy": "numpy",
        "BadMigrationLedger.record_export_synced": "sync",
        "BadMigrationLedger.record_import_sleepy": "sleep",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_migration_bad.py", src,
                                (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    assert not check_source(
        "hot_path_migration_bad.py", src,
        ("BadMigrationLedger.record_export_done_fine",))


def test_checker_accepts_clean_fixture():
    src = (_FIXTURES / "hot_path_good.py").read_text()
    findings = check_source("hot_path_good.py", src,
                            ("GoodBucket.refill", "GoodBucket.pick"))
    assert not findings, "\n".join(str(f) for f in findings)


def test_checker_flags_each_violation_class():
    src = (_FIXTURES / "hot_path_bad.py").read_text()
    cases = {
        "BadPolicy.device_work": "device",
        "BadPolicy.numpy_alloc": "numpy",
        "BadPolicy.blocking_sync": "sync",
        "BadPolicy.host_io": "I/O",
        "BadPolicy.wall_clock": "time.time",
        "BadPolicy.sleeper": "sleep",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_bad.py", src, (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    # the allowed monotonic clock must NOT fire
    assert not check_source("hot_path_bad.py", src,
                            ("BadPolicy.fine_actually",))


def test_checker_flags_missing_registration():
    findings = check_source("x.py", "def f():\n    pass\n",
                            ("DoesNotExist.method",))
    assert findings and "not found" in findings[0].message


def test_missing_registration_anchors_at_enclosing_class():
    """A registered qualname whose method was renamed reports at the
    ENCLOSING CLASS's line when the class still exists (line 1 only
    when even the class is gone)."""
    src = ("import os\n\n\n"
           "class Keeper:\n"
           "    def other(self):\n"
           "        pass\n")
    findings = check_source("x.py", src, ("Keeper.gone",))
    assert len(findings) == 1 and findings[0].line == 4
    findings = check_source("x.py", src, ("Vanished.gone",))
    assert len(findings) == 1 and findings[0].line == 1


# -- framework --------------------------------------------------------------

def test_pass_registry_has_all_four_checkers():
    assert set(registered_passes()) == {
        "hot-path", "lock-discipline", "dispatch-discipline",
        "lifecycle-discipline"}


def test_finding_renders_path_line_checker_symbol():
    f = Finding("a/b.py", 7, "lock-discipline", "C.m", "boom")
    assert str(f) == "a/b.py:7: [lock-discipline] [C.m] boom"


def test_run_analysis_over_repo_is_clean():
    """THE gate: all three checkers over the real serving stack, zero
    unsuppressed findings — and the deliberate exceptions really are
    carried as reasoned pragmas (suppressed is non-empty)."""
    report = run_analysis(str(_HERE.parent))
    assert report.ok, "\n".join(str(f) for f in report.findings)
    assert set(report.checkers) == set(registered_passes())
    assert report.suppressed, (
        "expected the serving stack's deliberate exceptions "
        "(sanctioned syncs, monitoring reads) to ride as pragmas")
    for f, reason in report.suppressed:
        assert reason.strip()


def test_run_analysis_checker_filter():
    report = run_analysis(str(_HERE.parent), checkers=["hot-path"])
    assert report.checkers == ("hot-path",)
    assert report.ok
    try:
        run_analysis(str(_HERE.parent), checkers=["nope"])
    except KeyError as exc:
        assert "nope" in str(exc)
    else:
        raise AssertionError("unknown checker id must raise")


# -- suppression pragmas ----------------------------------------------------

def test_pragma_silences_exactly_one_finding():
    """The suppression fixture has two identical sleep-under-lock
    violations in single-line statements; the reasoned pragma kills
    exactly the one it annotates, the unannotated one survives, and
    the reason-less pragma is itself a finding. (The multi-line case
    is test_pragma_covers_multiline_statement_extent.)"""
    src = (_FIXTURES / "suppression.py").read_text()
    raw = locks.check_source("suppression.py", src)
    sleeps = [f for f in raw if "sleep" in f.message]
    assert len(sleeps) == 4, [str(f) for f in raw]
    pragmas, bad = collect_pragmas("suppression.py", src)
    kept, suppressed = apply_pragmas(pragma_lines(pragmas), raw)
    assert len(suppressed) == 3
    assert all("sleep" in f.message for f, _ in suppressed)
    assert any("test fixture" in reason for _, reason in suppressed)
    assert sum("sleep" in f.message for f in kept) == 1
    # the reason-less pragma is a `pragma` finding and suppresses
    # nothing: the LD1 read it sits above must survive in `kept`
    assert len(bad) == 1 and bad[0].checker == "pragma"
    assert any(f.checker == "lock-discipline" and "_state" in f.message
               for f in kept)


def test_pragma_covers_multiline_statement_extent():
    """Regression: findings anchor at SUB-EXPRESSION lines — a pragma
    on a multi-line statement's first line must cover the whole
    lexical extent, not just its own line."""
    src = (_FIXTURES / "suppression.py").read_text()
    raw = locks.check_source("suppression.py", src)
    multiline = [f for f in raw
                 if f.symbol == "Suppressed.allowed_multiline"]
    assert len(multiline) == 2, [str(f) for f in raw]
    pragmas, bad = collect_pragmas("suppression.py", src)
    by_line = pragma_lines(pragmas)
    pragma_of = [p for p in pragmas
                 if "statement-extent" in p.reason][0]
    # both findings land BELOW the pragma's own line, inside the
    # statement's extent, and both are suppressed
    for f in multiline:
        assert f.line > pragma_of.line, (f.line, pragma_of.line)
        assert f.line in by_line and f.checker in by_line[f.line]
    kept, suppressed = apply_pragmas(by_line, multiline)
    assert not kept and len(suppressed) == 2


def test_pragma_inside_multiline_call_covers_the_call_line():
    """A comment-only pragma BETWEEN the continuation lines of a
    multi-line call (the paged server's grammar-table idiom) covers
    the whole statement, including the call's first line where some
    checkers anchor."""
    src = ("def f(self):\n"
           "    self.launch(\n"
           "        self.a,\n"
           "        # analysis: allow[hot-path] staged under _lock\n"
           "        self.b,\n"
           "    )\n")
    pragmas, bad = collect_pragmas("x.py", src)
    assert not bad
    by_line = pragma_lines(pragmas)
    for line in (2, 3, 4, 5, 6):
        assert "hot-path" in by_line.get(line, {}), (line, by_line)


def test_pragma_extent_survives_unparsable_source():
    """A syntax-broken file degrades to line-anchored coverage, never
    a traceback out of pragma collection."""
    src = ("def broken(:\n"
           "    x = 1  # analysis: allow[hot-path] still collected\n")
    pragmas, bad = collect_pragmas("x.py", src)
    assert not bad
    assert len(pragmas) == 1 and pragmas[0].covers == (2,)


def test_pragma_on_comment_line_covers_next_statement():
    pragmas, bad = collect_pragmas("x.py", (
        "# analysis: allow[hot-path] spans a\n"
        "# second comment line\n"
        "do_thing()\n"))
    assert not bad
    by_line = pragma_lines(pragmas)
    assert "hot-path" in by_line.get(1, {})
    assert "hot-path" in by_line.get(3, {})


def test_stale_pragma_is_a_finding(tmp_path):
    """A suppression whose checker ran but that matched nothing is
    rot: it would silently swallow the next finding on its line."""
    import cloud_server_tpu.analysis.locks as locks_mod
    clean = ("import threading\n"
             "class C:\n"
             "    def __init__(self):\n"
             "        self._lock = threading.Lock()\n"
             "    def fine(self):\n"
             "        # analysis: allow[lock-discipline] nothing here\n"
             "        return 1\n")
    target = tmp_path / "cloud_server_tpu" / "inference"
    target.mkdir(parents=True)
    for rel in locks_mod.LOCK_ROSTER:
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(clean if rel.endswith("qos.py")
                     else "X = 1\n", encoding="utf-8")
    report = run_analysis(str(tmp_path),
                          checkers=["lock-discipline"])
    assert any(f.checker == "pragma" and "stale" in f.message
               for f in report.findings), \
        [str(f) for f in report.findings]


def test_unknown_checker_pragma_is_a_finding(tmp_path):
    for rel in locks.LOCK_ROSTER:
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        body = "X = 1\n"
        if rel.endswith("slo.py"):
            body = "# analysis: allow[lockdiscipline] typo'd id\nX = 1\n"
        p.write_text(body, encoding="utf-8")
    report = run_analysis(str(tmp_path),
                          checkers=["lock-discipline"])
    assert any(f.checker == "pragma" and "unknown checker" in f.message
               for f in report.findings), \
        [str(f) for f in report.findings]


# -- lock-discipline --------------------------------------------------------

def test_locks_flags_each_violation_class():
    src = (_FIXTURES / "locks_bad.py").read_text()
    findings = locks.check_source("locks_bad.py", src)
    by_symbol = {}
    for f in findings:
        by_symbol.setdefault(f.symbol, []).append(f.message)
    cases = {
        "BadServer.peek_unlocked": ("read of _pending", "LD1"),
        "BadServer.reset_unlocked": ("write to _draining", "LD1"),
        "BadServer._split": ("split guard", "LD2"),
        "BadServer.sleepy_hold": ("sleep", "LD3"),
        "BadServer.sync_hold": ("device_get", "LD3"),
        "BadServer.io_hold": ("print", "LD3"),
        "BadServer.queue_hold": ("queue get with no timeout", "LD3"),
        "BadServer.backwards": ("_step_lock -> _lock order", "LD4"),
        "BadServer.backwards_oneliner": ("_step_lock -> _lock order",
                                         "LD4"),
        "BadServer._relock": ("self-deadlock", "LD4"),
    }
    for symbol, (needle, rule) in cases.items():
        msgs = by_symbol.get(symbol, [])
        assert any(needle in m and rule in m for m in msgs), (
            f"{symbol}: expected {needle!r} ({rule}); got {msgs} "
            f"(all: {[str(f) for f in findings]})")


def test_locks_accepts_disciplined_fixture():
    src = (_FIXTURES / "locks_good.py").read_text()
    findings = locks.check_source("locks_good.py", src)
    assert not findings, "\n".join(str(f) for f in findings)


def test_locks_roster_covers_acceptance_files():
    """The pass must keep auditing the serving modules the invariants
    live in — paged_server (both mutexes + ordering), router, qos."""
    for rel in ("cloud_server_tpu/inference/paged_server.py",
                "cloud_server_tpu/inference/router.py",
                "cloud_server_tpu/inference/qos.py"):
        assert rel in locks.LOCK_ROSTER, f"{rel} dropped from roster"
    assert locks.LOCK_ORDER == ("_step_lock", "_lock")


def test_locks_guard_inference_uses_must_held_call_sites():
    """A helper whose every call site holds the lock (the `_locked`
    suffix convention) inherits it — and a new lock-free caller
    demotes the helper's must-held set, so its writes to guarded
    state start flagging (the `_fail_all` -> `_release_slot` story
    that made the teardown path take the step lock)."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._x = 0\n"
        "    def set(self):\n"
        "        with self._lock:\n"
        "            self._x = 0\n"
        "    def _bump_locked(self):\n"
        "        self._x += 1\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._bump_locked()\n")
    assert not locks.check_source("c.py", src)
    # add an unlocked caller: the helper's must-held set collapses to
    # {} and its write to _lock-guarded _x becomes a violation
    leaky = src + ("    def leak(self):\n"
                   "        self._bump_locked()\n")
    findings = locks.check_source("c.py", leaky)
    assert any("write to _x" in f.message for f in findings), \
        [str(f) for f in findings]


# -- dispatch-discipline ----------------------------------------------------

_DISPATCH_LOOP = tuple(
    f"BadScheduler.{m}" for m in
    ("dispatch", "rogue_sync", "waiter", "scalarize", "hollow_commit",
     "bad_rounds", "bad_width", "good_rounds"))
_DISPATCH_SANCTIONED = ("BadScheduler.dispatch",
                        "BadScheduler.hollow_commit")


def test_dispatch_flags_each_violation_class():
    src = (_FIXTURES / "dispatch_bad.py").read_text()
    findings = dispatch.check_scheduler_source(
        "dispatch_bad.py", src, _DISPATCH_LOOP, _DISPATCH_SANCTIONED)
    by_symbol = {}
    for f in findings:
        by_symbol.setdefault(f.symbol, []).append(f.message)
    cases = {
        "BadScheduler.rogue_sync": "outside the sanctioned",
        "BadScheduler.waiter": "block_until_ready",
        "BadScheduler.scalarize": "item",
        "BadScheduler.hollow_commit": "sanction list has rotted",
        "BadScheduler.bad_rounds": "static argument 'n_rounds'",
        "BadScheduler.bad_width": "static argument 'width'",
    }
    for symbol, needle in cases.items():
        msgs = by_symbol.get(symbol, [])
        assert any(needle in m for m in msgs), (
            f"{symbol}: expected {needle!r}; got {msgs}")
    # the sanctioned sync and the bounded/bool static feeds are clean
    assert "BadScheduler.dispatch" not in by_symbol
    assert "BadScheduler.good_rounds" not in by_symbol


def test_dispatch_missing_roster_function_is_a_finding():
    src = (_FIXTURES / "dispatch_bad.py").read_text()
    findings = dispatch.check_scheduler_source(
        "dispatch_bad.py", src, ("BadScheduler.vanished",), ())
    assert findings and "not found" in findings[0].message
    assert findings[0].line > 1  # anchored at the class, not line 1


def test_dispatch_accepts_disciplined_fixture():
    src = (_FIXTURES / "dispatch_good.py").read_text()
    findings = dispatch.check_scheduler_source(
        "dispatch_good.py", src,
        ("GoodScheduler.step", "GoodScheduler._chunk_rounds"),
        ("GoodScheduler.step",))
    assert not findings, "\n".join(str(f) for f in findings)


def test_dispatch_host_policy_purity():
    src = (_FIXTURES / "dispatch_bad.py").read_text()
    findings = dispatch.check_host_policy_source("dispatch_bad.py", src)
    assert any("imports" in f.message for f in findings)
    clean = ("import threading\nimport time\n\n"
             "def policy(x):\n    return x + 1\n")
    assert not dispatch.check_host_policy_source("policy.py", clean)


def test_dispatch_rosters_cover_the_server():
    rel = "cloud_server_tpu/inference/paged_server.py"
    assert rel in dispatch.SCHEDULER_LOOPS
    assert dispatch.SANCTIONED_SYNCS[rel]
    for rel in ("cloud_server_tpu/inference/qos.py",
                "cloud_server_tpu/inference/slo.py",
                "cloud_server_tpu/inference/request_trace.py",
                "cloud_server_tpu/inference/spec_control.py",
                "cloud_server_tpu/utils/serving_metrics.py"):
        assert rel in dispatch.HOST_POLICY_MODULES


def test_dispatch_overlap_plan_release_free():
    """DD5: the async scheduler's plan path must not reach a
    page-releasing function — directly, or transitively through a
    same-class helper — while a dispatch may be in flight."""
    src = (
        "class S:\n"
        "    def _release_slot(self, sid):\n"
        "        pass\n"
        "    def _helper(self):\n"
        "        self._release_slot(0)\n"
        "    def _plan_iteration(self):\n"
        "        self._helper()\n"
        "    def _launch_plan(self, plan):\n"
        "        self.allocator.release([1])\n"
        "    def _overlap_sweep(self):\n"
        "        self.allocator.alloc(2)\n"
    )
    findings = dispatch.check_overlap_source(
        "s.py", src, ("S._plan_iteration", "S._launch_plan",
                      "S._overlap_sweep"))
    msgs = [f.message for f in findings]
    assert any("_release_slot" in m for m in msgs), msgs  # transitive
    assert any("allocator.release" in m for m in msgs), msgs  # direct
    assert all("DD5" in m for m in msgs)
    # alloc on the plan path is fine; the clean function is silent
    assert not [f for f in findings if f.symbol == "S._overlap_sweep"]


def test_dispatch_a_plan_with_nothing_in_flight_may_release():
    """DD5 is about a plan made under a dispatch in flight: what stands
    in the body of `if <the in-flight dispatch> is None:` runs with
    nothing in flight and may release pages. The else branch, a test
    on anything else, and a local bound twice stay policed."""
    def findings(body):
        src = ("class S:\n"
               "    def _extend_chains(self, n):\n"
               "        self._release_slot(0)\n"
               "    def _release_slot(self, sid):\n"
               "        pass\n"
               "    def _plan_iteration(self):\n" + body)
        return dispatch.check_overlap_source(
            "s.py", src, ("S._plan_iteration",))

    assert not findings(
        "        infl = self._inflight\n"
        "        if infl is None:\n"
        "            n = self._extend_chains(2)\n"
        "        else:\n"
        "            n = self._grow(2)\n")
    assert not findings(
        "        if self._inflight is None:\n"
        "            self._extend_chains(2)\n")
    for bad in (
            # the branch that runs under a dispatch in flight
            "        infl = self._inflight\n"
            "        if infl is None:\n"
            "            pass\n"
            "        else:\n"
            "            self._extend_chains(2)\n",
            # not the in-flight dispatch
            "        if self._ahead is None:\n"
            "            self._extend_chains(2)\n",
            # `is not None`
            "        if self._inflight is not None:\n"
            "            self._extend_chains(2)\n",
            # a local that is bound again
            "        infl = self._inflight\n"
            "        infl = None\n"
            "        if infl is None:\n"
            "            self._extend_chains(2)\n"):
        msgs = [f.message for f in findings(bad)]
        assert any("_extend_chains" in m and "DD5" in m
                   for m in msgs), (bad, msgs)


def test_dispatch_overlap_missing_plan_function_is_a_finding():
    findings = dispatch.check_overlap_source(
        "s.py", "class S:\n    pass\n", ("S._plan_iteration",))
    assert findings and "not found" in findings[0].message


def test_dispatch_overlap_roster_covers_the_async_scheduler():
    rel = "cloud_server_tpu/inference/paged_server.py"
    assert rel in dispatch.OVERLAP_PLAN_FUNCS
    quals = dispatch.OVERLAP_PLAN_FUNCS[rel]
    for want in ("PagedInferenceServer._plan_iteration",
                 "PagedInferenceServer._launch_plan",
                 "PagedInferenceServer._sweep",
                 "PagedInferenceServer._extend_chains_planned"):
        assert want in quals
    # the launch-ahead commit is a sanctioned sync, like every other
    # per-iteration commit point
    assert ("PagedInferenceServer._commit_inflight"
            in dispatch.SANCTIONED_SYNCS[rel])


def test_rosters_cover_disaggregation():
    """The disaggregation surfaces ride the same gates as the paths
    they extend: the router's role planner runs under the router lock
    inside every pick/submit (hot-path roster), and the paged
    server's handoff hooks run inside the scheduler iteration
    (scheduler-loop + overlap-plan rosters)."""
    router_quals = set(HOT_PATHS["cloud_server_tpu/inference/router.py"])
    for needed in ("ReplicatedRouter._role_candidates",
                   "ReplicatedRouter._prefill_load",
                   "ReplicatedRouter._plan_roles"):
        assert needed in router_quals, f"{needed} dropped from HOT_PATHS"
    rel = "cloud_server_tpu/inference/paged_server.py"
    loops = set(dispatch.SCHEDULER_LOOPS[rel])
    for needed in ("PagedInferenceServer._handoff_prefetch",
                   "PagedInferenceServer._drain_handoff_ready",
                   "PagedInferenceServer.pending_prefill_tokens",
                   "PagedInferenceServer.step"):
        assert needed in loops, f"{needed} dropped from SCHEDULER_LOOPS"
    assert ("PagedInferenceServer._handoff_prefetch"
            in dispatch.OVERLAP_PLAN_FUNCS[rel]), \
        "_handoff_prefetch dropped from the DD5 plan roster"


def test_dispatch_overlap_export_stays_out_of_plan_reach():
    """DD5 guards the disaggregation export: migrate_export evacuates
    the source slot (releases pages), so it must stay unreachable
    from the overlap plan path while a dispatch may be in flight.
    Fixture round-trip proving the checker fires on exactly that
    chain — and that the KV-prefetch shape the real
    _handoff_prefetch uses (gather + copy_to_host_async, no release)
    stays silent."""
    src = (
        "class S:\n"
        "    def _release_slot(self, sid):\n"
        "        pass\n"
        "    def _evacuate_request_locked(self, req):\n"
        "        self._release_slot(0)\n"
        "    def migrate_export(self, req):\n"
        "        self._evacuate_request_locked(req)\n"
        "    def _handoff_prefetch(self, sel):\n"
        "        self.migrate_export(None)\n"
        "    def _handoff_prefetch_fine(self, sel):\n"
        "        buf = self.kv.gather(sel)\n"
        "        buf.copy_to_host_async()\n"
    )
    findings = dispatch.check_overlap_source(
        "s.py", src, ("S._handoff_prefetch", "S._handoff_prefetch_fine"))
    msgs = [f.message for f in findings]
    assert any("_release_slot" in m for m in msgs), msgs
    assert all("DD5" in m for m in msgs)
    assert not [f for f in findings
                if f.symbol == "S._handoff_prefetch_fine"], msgs


# -- lifecycle-discipline ---------------------------------------------------

# fixture-local rosters for the lifecycle round-trips, mirroring how
# the real rosters key on the audited modules
_LC_GOOD_KW = dict(owner_funcs=("GoodOwner.retry",),
                   marker_funcs=("GoodLifecycle.emit",),
                   complete_funcs=("GoodLifecycle._complete",),
                   transfer_funcs=("SlotRecord",))
_LC_BAD_KW = dict(owner_funcs=(), marker_funcs=(),
                  complete_funcs=("BadFinish._complete",),
                  transfer_funcs=())


def test_lifecycle_flags_each_violation_class():
    """lifecycle_bad.py: one violation per method, each must fire —
    LC1 (leak, path-sensitive early exit, double complete, rogue
    _done.set/_on_done), LC2 (misordered and missing markers), LC3
    (leak on return, leak on raise, dropped result, rebind while
    live), LC4 (may-raise call and explicit raise between guarded
    writes)."""
    src = (_FIXTURES / "lifecycle_bad.py").read_text()
    findings = lifecycle.check_source("lifecycle_bad.py", src,
                                      **_LC_BAD_KW)
    by_symbol = {}
    for f in findings:
        by_symbol.setdefault(f.symbol, []).append(f.message)
    expected = {
        "BadFinish.drop_on_floor": ("never reaches _complete", "LC1"),
        "BadFinish.early_exit_leaks": ("return", "LC1"),
        "BadFinish.double_complete": ("completed again", "LC1"),
        "BadFinish.rogue_done_set": ("_done.set() outside", "LC1"),
        "BadFinish.rogue_callback": ("_on_done is read", "LC1"),
        "BadOrder._complete": ("runs before", "LC2"),
        "BadMissing._complete": ("missing the _fail_handler", "LC2"),
        "BadPages.leak_on_return": ("never releases", "LC3"),
        "BadPages.leak_on_raise": ("raise", "LC3"),
        "BadPages.drops_result": ("discarded", "LC3"),
        "BadPages.rebinds_while_live": ("rebound", "LC3"),
        "BadTear.risky_between": ("may-raise call open()", "LC4"),
        "BadTear.raise_between": ("an explicit raise", "LC4"),
    }
    for symbol, (needle, rule) in expected.items():
        msgs = by_symbol.get(symbol, [])
        assert any(needle in m and rule in m for m in msgs), (
            symbol, msgs or "NO FINDINGS")
    # exactly one finding per violation method — no noise
    assert set(by_symbol) == set(expected), sorted(by_symbol)
    for symbol, msgs in by_symbol.items():
        assert len(msgs) == 1, (symbol, msgs)


def test_lifecycle_accepts_disciplined_fixture():
    """lifecycle_good.py holds the compliant twin of every violation
    (direct/transitive/deferred completion, sanctioned owner and
    marker, balanced/transferred/returned pages, protected or
    relocated risky work) — the checker must stay silent."""
    src = (_FIXTURES / "lifecycle_good.py").read_text()
    findings = lifecycle.check_source("lifecycle_good.py", src,
                                      **_LC_GOOD_KW)
    assert not findings, "\n".join(str(f) for f in findings)


def test_lifecycle_completion_via_call_graph():
    """A path completing through a helper that transitively reaches
    _complete (the class-local call-graph propagation) is clean; the
    same path without the helper edge is a leak."""
    good = (
        "class S:\n"
        "    def _complete(self, req):\n"
        "        self.metrics.observe_finish(req)\n"
        "        h = self._fail_handler\n"
        "        req._done.set()\n"
        "        cb = req._on_done\n"
        "    def _finish(self, req):\n"
        "        self._deactivate(req)\n"
        "        self._complete(req)\n"
        "    def expire(self, req):\n"
        "        req.finish_reason = 'deadline'\n"
        "        self._finish(req)\n")
    assert not lifecycle.check_source(
        "s.py", good, owner_funcs=(), marker_funcs=(),
        complete_funcs=(), transfer_funcs=())
    bad = good.replace("self._complete(req)",
                       "self._deactivate(req)")
    findings = lifecycle.check_source(
        "s.py", bad, owner_funcs=(), marker_funcs=(),
        complete_funcs=(), transfer_funcs=())
    assert any("never reaches _complete" in f.message
               for f in findings), [str(f) for f in findings]


_DEFERRED_SRC = (
    "class S:\n"
    "    def _complete(self, req):\n"
    "        self.metrics.observe_finish(req)\n"
    "        h = self._fail_handler\n"
    "        req._done.set()\n"
    "        cb = req._on_done\n"
    "    def _complete_later(self, req):\n"
    "        self._deliveries.append((req, None))\n"
    "    def _deliver(self):\n"
    "        out, self._deliveries = self._deliveries, []\n"
    "        for req, token in out:\n"
    "            if token is None:\n"
    "                self._complete(req)\n"
    "            else:\n"
    "                req.stream(token)\n"
    "    def _finish(self, req):\n"
    "        self._complete_later(req)\n"
    "    def reap(self, req, cancelled):\n"
    "        req.finish_reason = 'cancelled'\n"
    "        if cancelled:\n"
    "            self._finish(req)\n"
    "            return\n"
    "        self._complete_later(req)\n")
_DEFERRED_KW = dict(owner_funcs=(), marker_funcs=(), complete_funcs=(),
                    transfer_funcs=())
_DEFERRED = {"S._complete_later": ("_deliveries", "S._deliver")}


@pytest.mark.parametrize("edit,needle", [
    (None, None),
    # a path that stops putting the request on the list leaks it: the
    # list is no waiver, LC1 sees through it
    (("        self._complete_later(req)\n\n", "        pass\n\n"),
     "never reaches _complete"),
    # completed at once AND put on the list: twice
    (("            self._finish(req)\n            return\n",
      "            self._complete(req)\n"), "completed again"),
    # rot: the deferring method no longer appends to its list
    (("self._deliveries.append((req, None))", "self.count += 1"),
     "no longer appends to self._deliveries"),
    # rot: the drain no longer runs the list through _complete
    (("                self._complete(req)\n", "                pass\n"),
     "never complete"),
    # rot: the drain is gone
    (("def _deliver(self)", "def _drain(self)"), "does not exist"),
])
def test_lifecycle_completion_through_the_delivery_list(edit, needle):
    """The paged server's commit completes a request by putting it on
    the delivery list (`DEFERRED_COMPLETION_FUNCS`): a call to the
    rostered method, direct or through `_finish`, IS the completion
    for LC1, and the roster rots loudly."""
    src = _DEFERRED_SRC + "\n"
    if edit is not None:
        assert src.count(edit[0]) == 1, edit[0]
        src = src.replace(*edit)
    findings = lifecycle.check_source("s.py", src, **_DEFERRED_KW,
                                      deferred_funcs=_DEFERRED)
    if needle is None:
        assert not findings, [str(f) for f in findings]
        # without the roster the same source leaks on every path
        bare = lifecycle.check_source("s.py", src, **_DEFERRED_KW,
                                      deferred_funcs={})
        assert any("never reaches _complete" in f.message for f in bare)
    else:
        assert any(needle in f.message for f in findings), \
            [str(f) for f in findings]


def test_lifecycle_roster_rot_is_a_finding():
    """Roster entries that vanished, and entries whose sanctioned
    behavior vanished (an owner without _done.set(), a marker that no
    longer assigns finish_reason), must each surface."""
    src = ("class R:\n"
           "    def retry(self, orig):\n"
           "        orig.cancel()\n"
           "    def emit(self, req):\n"
           "        return False\n")
    findings = lifecycle.check_source(
        "r.py", src,
        owner_funcs=("R.retry", "R.gone"),
        marker_funcs=("R.emit",),
        complete_funcs=("R._complete",),
        transfer_funcs=("RSlot",))
    msgs = [f.message for f in findings]
    assert any("R.gone" in m and "does not exist" in m
               for m in msgs), msgs
    assert any("no longer contains a _done.set()" in m
               for m in msgs), msgs
    assert any("no longer assigns finish_reason" in m
               for m in msgs), msgs
    assert any("COMPLETE_FUNCS" in m or "R._complete" in m
               for m in msgs), msgs
    assert any("RSlot" in m for m in msgs), msgs


def test_lifecycle_rosters_cover_the_serving_stack():
    """The real rosters stay anchored: the five lifecycle modules,
    the router's completion owners, emit_token as the terminal
    marker, the _complete body, and _Slot as the audited page
    transferee. check_lifecycle over the repo is clean (deliberate
    exceptions ride as pragmas, applied by run_analysis)."""
    assert lifecycle.LIFECYCLE_ROSTER == (
        "cloud_server_tpu/inference/paged_server.py",
        "cloud_server_tpu/inference/request.py",
        "cloud_server_tpu/inference/block_allocator.py",
        "cloud_server_tpu/inference/migration.py",
        "cloud_server_tpu/inference/router.py")
    owners = lifecycle.COMPLETION_OWNER_FUNCS[
        "cloud_server_tpu/inference/router.py"]
    assert "ReplicatedRouter._retry_submit" in owners
    assert "ReplicatedRouter._mirror_retry" in owners
    assert lifecycle.TERMINAL_MARKER_FUNCS[
        "cloud_server_tpu/inference/request.py"] == ("emit_token",)
    assert lifecycle.OWNERSHIP_TRANSFER_FUNCS[
        "cloud_server_tpu/inference/paged_server.py"] == ("_Slot",)
    # the commit completes through the delivery list, and the list's
    # two ends are on the scheduler-loop and hot-path rosters
    rel = "cloud_server_tpu/inference/paged_server.py"
    assert lifecycle.DEFERRED_COMPLETION_FUNCS[rel] == {
        "PagedInferenceServer._complete_later":
            ("_deliveries", "PagedInferenceServer._deliver")}
    for qual in ("PagedInferenceServer._complete_later",
                 "PagedInferenceServer._deliver"):
        assert qual in dispatch.SCHEDULER_LOOPS[rel]
        assert qual in HOT_PATHS[rel]
    report = run_analysis(str(_HERE.parent),
                          checkers=["lifecycle-discipline"])
    assert report.ok, "\n".join(str(f) for f in report.findings)


def test_analysis_latency_budget():
    """The gate runs inside every test process AND as an explicit
    run_tests.sh step: all passes over the full roster must finish
    far under the tier-1 margin."""
    t0 = time.perf_counter()
    report = run_analysis(str(_HERE.parent))
    elapsed = time.perf_counter() - t0
    assert report.ok
    assert elapsed < 10.0, f"analysis suite took {elapsed:.1f}s"


# -- reporters / CLI --------------------------------------------------------

def test_json_report_shape_is_stable():
    """External tooling consumes --json: the top-level keys, the
    finding fields, and the version tag are load-bearing."""
    report = run_analysis(str(_HERE.parent))
    doc = report_json(report)
    assert set(doc) == {"version", "root", "checkers", "counts",
                        "findings", "suppressed"}
    assert doc["version"] == 1
    assert set(doc["counts"]) == {"findings", "suppressed"}
    assert doc["counts"]["findings"] == 0
    assert doc["counts"]["suppressed"] == len(doc["suppressed"])
    for entry in doc["suppressed"]:
        assert set(entry) == {"path", "line", "checker", "symbol",
                              "message", "reason"}
    assert json.loads(json.dumps(doc)) == doc  # round-trips as JSON


def test_cli_runs_clean_and_emits_json():
    out = subprocess.run(
        [sys.executable, "-m", "cloud_server_tpu.analysis", "--json",
         str(_HERE.parent)],
        capture_output=True, text=True, cwd=str(_HERE.parent))
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["counts"]["findings"] == 0
    assert sorted(doc["checkers"]) == sorted(registered_passes())


def test_cli_unknown_checker_is_usage_error():
    out = subprocess.run(
        [sys.executable, "-m", "cloud_server_tpu.analysis",
         "--checker", "bogus", str(_HERE.parent)],
        capture_output=True, text=True, cwd=str(_HERE.parent))
    assert out.returncode == 2
    assert "bogus" in out.stderr


def test_cli_lifecycle_checker_filter_round_trip():
    """--checker lifecycle-discipline runs ONLY the new pass over the
    real stack and exits clean."""
    out = subprocess.run(
        [sys.executable, "-m", "cloud_server_tpu.analysis", "--json",
         "--checker", "lifecycle-discipline", str(_HERE.parent)],
        capture_output=True, text=True, cwd=str(_HERE.parent))
    assert out.returncode == 0, out.stderr or out.stdout
    doc = json.loads(out.stdout)
    assert doc["checkers"] == ["lifecycle-discipline"]
    assert doc["counts"]["findings"] == 0


def test_cli_emits_sarif():
    """--sarif writes a SARIF 2.1.0 document CI can render as code
    annotations: schema/version pinned, one rule per checker."""
    out = subprocess.run(
        [sys.executable, "-m", "cloud_server_tpu.analysis", "--sarif",
         str(_HERE.parent)],
        capture_output=True, text=True, cwd=str(_HERE.parent))
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["version"] == "2.1.0"
    assert "sarif-2.1.0" in doc["$schema"]
    (run,) = doc["runs"]
    assert run["tool"]["driver"]["name"] == "cloud_server_tpu.analysis"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert rule_ids == set(registered_passes())
    assert run["results"] == []  # clean tree: no annotations


def test_sarif_results_carry_location_and_level():
    """Findings map to SARIF results with ruleId, error level, and a
    physical location (path + startLine) — the fields annotation
    renderers key on."""
    report = run_analysis(str(_HERE.parent))
    fake = Finding("pkg/mod.py", 41, "lifecycle-discipline", "C.m",
                   "boom (LC1)")
    report.findings.append(fake)
    doc = report_sarif(report)
    (res,) = doc["runs"][0]["results"]
    assert res["ruleId"] == "lifecycle-discipline"
    assert res["level"] == "error"
    assert "[C.m] boom (LC1)" == res["message"]["text"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "pkg/mod.py"
    assert loc["region"]["startLine"] == 41
    assert json.loads(json.dumps(doc)) == doc


def test_cli_json_and_sarif_are_mutually_exclusive():
    out = subprocess.run(
        [sys.executable, "-m", "cloud_server_tpu.analysis", "--json",
         "--sarif", str(_HERE.parent)],
        capture_output=True, text=True, cwd=str(_HERE.parent))
    assert out.returncode == 2
    assert "not allowed with" in out.stderr


# -- docs drift -------------------------------------------------------------

def test_checker_catalog_matches_docs():
    """Every registered checker id appears in docs/analysis.md's
    catalog, and vice versa — the catalog cannot rot in either
    direction (the observability metric-catalog rule, applied to
    checkers). The implicit `pragma` id is documented too."""
    doc = (_HERE.parent / "docs" / "analysis.md").read_text()
    catalog = set(re.findall(r"^\|\s*`([a-z0-9-]+)`", doc, re.M))
    runtime = set(registered_passes()) | {"pragma"}
    missing = runtime - catalog
    stale = catalog - runtime
    assert not missing, (
        f"registered but absent from docs/analysis.md: {sorted(missing)}")
    assert not stale, (
        f"documented but never registered: {sorted(stale)}")
    assert "analysis: allow[" in doc  # the pragma syntax is documented


def test_locks_bounded_acquire_idiom_counts_as_held():
    """`got = self._lock.acquire(timeout=...)` marks the rest of the
    block as holding the lock — the teardown idiom `_fail_all` uses —
    so guarded writes there stay clean, and the acquisition still
    participates in ordering/self-deadlock checks."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._x = 0\n"
        "    def set(self):\n"
        "        with self._lock:\n"
        "            self._x = 1\n"
        "    def teardown(self):\n"
        "        got = self._lock.acquire(timeout=5.0)\n"
        "        try:\n"
        "            self._x = 0\n"
        "        finally:\n"
        "            if got:\n"
        "                self._lock.release()\n")
    assert not locks.check_source("c.py", src), \
        [str(f) for f in locks.check_source("c.py", src)]
    # and a bounded acquire of a lock that MAY already be held still
    # flags as a self-deadlock hazard
    nested = src + ("    def outer(self):\n"
                    "        with self._lock:\n"
                    "            self.teardown()\n")
    findings = locks.check_source("c.py", nested)
    assert any("self-deadlock" in f.message for f in findings), \
        [str(f) for f in findings]


def test_dispatch_checks_positional_and_splatted_statics():
    """Static args passed positionally map onto the callee's param
    names; a **-splat is opaque and flags by itself."""
    src = (
        "from functools import partial\n"
        "import jax\n"
        "def _core(x, n_rounds, *, cfg=None):\n"
        "    return x\n"
        "_jit = partial(jax.jit, static_argnames=('n_rounds', 'cfg'))"
        "(_core)\n"
        "class S:\n"
        "    def loop_pos(self, prompt):\n"
        "        return _jit(prompt, len(prompt), cfg=None)\n"
        "    def loop_splat(self, prompt, kw):\n"
        "        return _jit(prompt, 2, **kw)\n"
        "    def loop_ok(self, prompt):\n"
        "        return _jit(prompt, 4, cfg=self.cfg)\n")
    findings = dispatch.check_scheduler_source(
        "s.py", src, ("S.loop_pos", "S.loop_splat", "S.loop_ok"), ())
    msgs = [f.message for f in findings]
    assert any("'n_rounds'" in m and f.symbol == "S.loop_pos"
               for f, m in zip(findings, msgs)), msgs
    assert any("**-splat" in m for m in msgs), msgs
    assert not [f for f in findings if f.symbol == "S.loop_ok"], msgs


def test_boundedness_tracks_walrus_assignments():
    """`(n := len(prompt))` binds like an assignment: an unbounded
    walrus-bound name must not slip past DD4."""
    src = (
        "from functools import partial\n"
        "import jax\n"
        "def _core(x, *, n_rounds: int):\n"
        "    return x\n"
        "_jit = partial(jax.jit, static_argnames=('n_rounds',))(_core)\n"
        "class S:\n"
        "    def loop(self, prompt):\n"
        "        if (n := len(prompt)) > 0:\n"
        "            return _jit(prompt, n_rounds=n)\n"
        "        return None\n")
    findings = dispatch.check_scheduler_source("s.py", src,
                                               ("S.loop",), ())
    assert any("'n_rounds'" in f.message for f in findings), \
        [str(f) for f in findings]


def test_missing_rostered_file_is_a_finding_not_a_traceback(tmp_path):
    """A deleted/renamed rostered file (or a wrong root) must surface
    as findings through the normal report, never as an unhandled
    FileNotFoundError out of the gating step."""
    report = run_analysis(str(tmp_path))
    assert not report.ok
    assert all("cannot be read" in f.message for f in report.findings)
    assert {f.checker for f in report.findings} == set(
        registered_passes())


def test_locks_oneliner_double_acquire_is_self_deadlock():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def twice(self):\n"
        "        with self._lock, self._lock:\n"
        "            return 1\n")
    findings = locks.check_source("c.py", src)
    assert any("self-deadlock" in f.message for f in findings), \
        [str(f) for f in findings]


def test_dispatch_nonliteral_static_argnames_is_a_finding():
    """`static_argnames=SOME_CONSTANT` defeats the boundedness
    analysis — that must surface as 'cannot be verified', never as a
    silent skip of every DD4 check for that callable."""
    src = (
        "from functools import partial\n"
        "import jax\n"
        "STATICS = ('n_rounds',)\n"
        "def _core(x, *, n_rounds: int):\n"
        "    return x\n"
        "_jit = partial(jax.jit, static_argnames=STATICS)(_core)\n"
        "class S:\n"
        "    def loop(self, prompt):\n"
        "        return _jit(prompt, n_rounds=len(prompt))\n")
    findings = dispatch.check_scheduler_source("s.py", src,
                                               ("S.loop",), ())
    assert any("not a literal" in f.message for f in findings), \
        [str(f) for f in findings]


def test_checker_flags_bad_scenario_paths():
    """Fixture round-trip proving the checker is LIVE on the scenario
    harness's violation shapes: a tick that reads the wall clock, a
    tick that sleeps until the next event, firing lag through a numpy
    buffer, logging every rejection from the firing path, printing
    the autoscaler decision — each must fire; the plain list/float
    event-pop shape the real tick() uses must not."""
    src = (_FIXTURES / "hot_path_scenarios_bad.py").read_text()
    cases = {
        "BadDriver.tick_reads_clock": "time.time",
        "BadDriver.tick_sleeps": "sleep",
        "BadDriver.fire_numpy_lag": "numpy",
        "BadDriver.fire_logged": "logging",
        "BadDriver.evaluate_prints": "I/O",
    }
    for qual, needle in cases.items():
        findings = check_source("hot_path_scenarios_bad.py", src,
                                (qual,))
        assert findings, f"{qual}: expected a finding"
        assert any(needle in f.message for f in findings), \
            f"{qual}: {[str(f) for f in findings]}"
    assert not check_source("hot_path_scenarios_bad.py", src,
                            ("BadDriver.tick_fine",))


def test_registry_covers_scenarios():
    """The scenario harness rides both static passes: the replay
    driver's firing path and the autoscaler's decision path are
    hot-path rostered, and all four scenarios modules are DD3
    host-policy (the simulator MODELS device iterations from fitted
    flight-record costs — it must never run one)."""
    replay = "cloud_server_tpu/scenarios/replay.py"
    asc = "cloud_server_tpu/scenarios/autoscaler.py"
    for needed in ("ReplayDriver.tick", "ReplayDriver._fire"):
        assert needed in HOT_PATHS[replay], \
            f"{needed} dropped from HOT_PATHS"
    for needed in ("SLOBurnAutoscaler.evaluate",
                   "SLOBurnAutoscaler._burn_signal"):
        assert needed in HOT_PATHS[asc], \
            f"{needed} dropped from HOT_PATHS"
    for rel in ("cloud_server_tpu/scenarios/workload.py",
                replay,
                "cloud_server_tpu/scenarios/simulator.py",
                asc):
        assert rel in dispatch.HOST_POLICY_MODULES, \
            f"{rel} dropped from the DD3 host-policy roster"
