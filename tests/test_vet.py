"""tidb-vet static-analysis suite + lockwatch runtime detector (ISSUE 7
seeded it; ISSUE 9 added the interprocedural dataflow passes, the jaxpr
auditor, the stale-suppression audit and result caching): every pass
flags its true-positive fixture in tests/vet_fixtures/, the live tree is
clean, suppression markers work (and rot is flagged), the CLI contract
holds (exit 0 on the tree, nonzero on the corpus, --json parses,
baseline/diff round-trips), and the chaos / PD / replication-catch-up
storms run under lockwatch with zero lock-order cycles and zero
unguarded annotated accesses."""

import json
import os
import subprocess
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "vet_fixtures")
sys.path.insert(0, os.path.join(REPO, "tools"))

from tidb_tpu import analysis
from tidb_tpu.analysis import dataflow, guards, jaxaudit, lockwatch, suppress_audit
from tidb_tpu.analysis.common import SourceFile


def _fixture(name: str) -> SourceFile:
    return SourceFile.load(os.path.join(FIXTURES, name), repo=REPO)


def _messages(findings):
    return [f.render() for f in findings]


# ------------------------------------------------- fixtures: true positives

class TestFixtureCorpus:
    def test_jit_purity_flags_fixture(self):
        found = analysis.run_pass("jit-purity", [_fixture("jit_purity_bad.py")])
        names = " ".join(_messages(found))
        assert len(found) == 3, names
        assert "BAD_CONST" in names and "BAD_DERIVED" in names
        assert "mutates global jax config" in names

    def test_lock_discipline_flags_fixture(self):
        found = analysis.run_pass("lock-discipline", [_fixture("lock_bad.py")])
        msgs = _messages(found)
        assert len(found) == 2, msgs
        assert any("written outside" in m for m in msgs)
        assert any("read outside" in m for m in msgs)
        # the `# requires: _mu` helper and the locked bump stay clean
        assert not any(":15:" in m or ":24:" in m for m in msgs)

    def test_dataflow_snapshot_flags_fixture(self):
        found = analysis.run_pass("dataflow-snapshot", [_fixture("dataflow_snapshot_bad.py")])
        msgs = _messages(found)
        assert len(found) == 4, msgs
        assert any("max_ts" in m and "NEWEST version" in m for m in msgs)
        assert any("latest-version ts (12345)" in m for m in msgs)
        assert any("does not flow" in m for m in msgs)
        # the disciplined reads stay clean: req.start_ts direct (line 30)
        # and start_ts flowing through helper_scan (lines 35/38)
        assert not any(f.line in (30, 35, 38) for f in found)

    def test_dataflow_backoff_flags_fixture(self):
        found = analysis.run_pass("dataflow-backoff", [_fixture("dataflow_backoff_bad.py")])
        msgs = _messages(found)
        assert len(found) == 2, msgs
        assert any("never consults a Backoffer budget" in m for m in msgs)
        assert any("raw time.sleep" in m for m in msgs)

    def test_dataflow_closure_findings_not_duplicated(self, tmp_path):
        """A violation inside a nested closure reports ONCE: the closure
        is its own FuncInfo, so the parent's walk must not re-cover it
        (review fix: both used to report the same line)."""
        p = tmp_path / "m.py"
        p.write_text(
            "import time\n\n"
            "def select(store, req):  # vet: request-path-root\n"
            "    def worker():\n"
            "        time.sleep(0.05)\n"
            "    run(worker)\n")
        sf = SourceFile.load(str(p), repo=str(tmp_path))
        found = analysis.run_pass("dataflow-backoff", [sf])
        assert len(found) == 1 and found[0].line == 5, _messages(found)

    def test_escape_lexical_floor_covers_control_plane(self, tmp_path):
        """The old error-taxonomy guarantee survives the promotion: a
        bare raise in a dispatch/store/PD-layer file is a finding even
        OUTSIDE the request cone (PD ticks/schedulers)."""
        (tmp_path / "tidb_tpu" / "pd").mkdir(parents=True)
        root = tmp_path / "root.py"
        root.write_text("def select(store, req):  # vet: request-path-root\n"
                        "    return None\n")
        sched = tmp_path / "tidb_tpu" / "pd" / "sched.py"
        sched.write_text("def tick():\n    raise RuntimeError('boom')\n")
        files = [SourceFile.load(str(root), repo=str(tmp_path)),
                 SourceFile.load(str(sched), repo=str(tmp_path))]
        found = analysis.run_pass("dataflow-error-escape", files)
        assert len(found) == 1, _messages(found)
        assert "dispatch/store/PD layer" in found[0].message

    def test_dataflow_escape_flags_fixture(self):
        found = analysis.run_pass("dataflow-error-escape", [_fixture("dataflow_escape_bad.py")])
        msgs = _messages(found)
        assert len(found) == 2, msgs
        assert any("bare `raise RuntimeError` escapes" in m for m in msgs)
        assert any("RegionTimeoutError" in m and "session boundary" in m for m in msgs)

    def test_jax_audit_flags_fixture(self):
        found = analysis.run_pass("jax-audit", [_fixture("jaxaudit_bad.py")])
        msgs = _messages(found)
        assert len(found) == 3, msgs
        assert any("float64 leaked into an integer-only program" in m for m in msgs)
        assert any("DIFFERENT jaxprs" in m and "closure-captured" in m for m in msgs)
        assert any("'baked-literal'" in m and "two different literal values" in m for m in msgs)

    def test_metrics_flags_fixture(self):
        found = analysis.run_pass("metrics", [_fixture("metrics_bad.py")])
        msgs = " | ".join(_messages(found))
        for expect in (
            "registered more than once",
            "must end `_total`",
            "invalid metric name",
            "must not claim the counter suffix",
            "takes 1 label value(s)",
            "is a labeled family",
            "has no .labels()",
            "not a registered instrument",
        ):
            assert expect in msgs, f"missing {expect!r} in {msgs}"

    def test_wire_parity_flags_fixture(self):
        found = analysis.run_pass("wire-parity", [_fixture("bad_wire.py")])
        msgs = " | ".join(_messages(found))
        assert "encode_orphan has no matching decode_orphan" in msgs
        assert "field-kind mismatch" in msgs and "'f64'" in msgs
        assert "sub-structure mismatch" in msgs

    def test_failpoints_flags_fixture(self):
        from tidb_tpu.analysis import failpoints

        uses = failpoints._scan(
            failpoints._USE, [os.path.join(FIXTURES, "failpoint_bad.py")])
        assert "vetfix/undefined-name" in uses
        _findings, sites = failpoints.analyze()
        # the armed name resolves to no site — exactly what the pass flags
        assert "vetfix/undefined-name" not in sites
        # ... and the live-tree run must NOT scan the fixture corpus
        assert not any("vet_fixtures" in w for ws in sites.values() for w in ws)


# ------------------------------------------------- live tree + suppression

class TestLiveTree:
    def test_every_pass_clean_on_the_tree(self):
        findings = analysis.run_all()
        assert findings == [], "\n".join(_messages(findings))

    def test_suppression_marker_drops_finding(self, tmp_path):
        p = tmp_path / "sup.py"
        p.write_text(
            "import threading\n\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self.v = 0  # guarded_by: _mu\n\n"
            "    def racy(self):\n"
            "        return self.v  # vet: ignore[lock-discipline]\n\n"
            "    def racy2(self):\n"
            "        return self.v\n"
        )
        sf = SourceFile.load(str(p), repo=str(tmp_path))
        found = analysis.run_pass("lock-discipline", [sf])
        assert len(found) == 1 and found[0].line == 12  # only the unmarked one

    def test_stale_suppression_flagged(self, tmp_path):
        p = tmp_path / "s.py"
        p.write_text("x = 1  # vet: ignore[jit-purity]\n"
                     "y = 2  # vet: ignore[no-such-pass]\n")
        sf = SourceFile.load(str(p), repo=str(tmp_path))
        out = suppress_audit.audit(
            [sf], used_markers=set(), ran_passes={"jit-purity"},
            known_passes={"jit-purity"})
        msgs = [f.message for f in out]
        assert len(out) == 2, msgs
        assert any("stale suppression" in m for m in msgs)
        assert any("unknown pass 'no-such-pass'" in m for m in msgs)

    def test_live_suppression_not_flagged(self, tmp_path):
        """A marker that actually suppressed a finding is live — the
        audit subtracts the used-marker set the filter recorded."""
        from tidb_tpu.analysis.common import filter_suppressed

        p = tmp_path / "s.py"
        p.write_text(
            "import threading\n\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self.v = 0  # guarded_by: _mu\n\n"
            "    def racy(self):\n"
            "        return self.v  # vet: ignore[lock-discipline]\n")
        sf = SourceFile.load(str(p), repo=str(tmp_path))
        from tidb_tpu.analysis import lock_discipline

        used: set = set()
        kept = filter_suppressed(lock_discipline.run([sf]), {sf.rel: sf}, used)
        assert kept == [] and used  # the marker earned its keep
        out = suppress_audit.audit(
            [sf], used_markers=used, ran_passes={"lock-discipline"},
            known_passes={"lock-discipline"})
        assert out == [], [f.message for f in out]

    def test_pass_not_run_gives_no_verdict(self, tmp_path):
        p = tmp_path / "s.py"
        p.write_text("x = 1  # vet: ignore[jit-purity]\n")
        sf = SourceFile.load(str(p), repo=str(tmp_path))
        out = suppress_audit.audit(
            [sf], used_markers=set(), ran_passes=set(),
            known_passes={"jit-purity"})
        assert out == []

    def test_guard_collection_reads_the_conventions(self):
        sf = SourceFile.load(os.path.join(REPO, "tidb_tpu", "store", "store.py"))
        g = guards.collect(sf.tree, sf.lines)
        assert g.classes["TPUStore"]["_cop_cache"] == "_cop_lock"
        assert g.classes["TPUStore"]["_write_ver"] == "_cop_lock"
        sf = SourceFile.load(os.path.join(REPO, "tidb_tpu", "store", "kv.py"))
        g = guards.collect(sf.tree, sf.lines)
        assert g.classes["MemKV"]["_data"] == "lock"
        assert ("MemKV", "_ensure_sorted") in g.requires


# ------------------------------------------------- CLI contract

class TestVetCLI:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "vet.py"), *args],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

    def test_clean_tree_exits_zero_and_json_parses(self):
        r = self._run("--json")
        assert r.returncode == 0, r.stdout + r.stderr
        assert json.loads(r.stdout) == []

    def test_fixture_corpus_exits_nonzero_with_diffable_json(self):
        fixtures = sorted(
            os.path.join(FIXTURES, f) for f in os.listdir(FIXTURES) if f.endswith(".py"))
        r = self._run("--json", "--files", *fixtures)
        assert r.returncode == 1, r.stdout + r.stderr
        findings = json.loads(r.stdout)
        assert findings, "fixture corpus produced no findings"
        assert {f["pass"] for f in findings} >= {
            "jit-purity", "lock-discipline", "metrics", "wire-parity",
            "dataflow-snapshot", "dataflow-backoff", "dataflow-error-escape",
            "jax-audit"}
        assert all({"path", "line", "pass", "message"} <= set(f) for f in findings)

    def test_only_accepts_globs(self):
        r = self._run("--only", "dataflow-*")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "dataflow-snapshot" in r.stdout and "dataflow-error-escape" in r.stdout

    def test_only_suppressions_runs_the_full_suite(self):
        """The stale-marker audit needs every pass's verdict: --only
        suppressions triggers a full run and reports just that pass
        (review fix: it used to be rejected as an unknown pass that
        --list itself advertised)."""
        r = self._run("--only", "suppressions")
        assert r.returncode == 0, r.stdout + r.stderr
        with pytest.raises(ValueError, match="run_all"):
            analysis.run_pass("suppressions")

    def test_diff_is_a_multiset(self):
        """A SECOND instance of an identical-message defect in the same
        file is a NEW finding (review fix: a set-diff waved it through
        the gate)."""
        import vet

        a = {"path": "p.py", "line": 3, "pass": "x", "message": "m"}
        a2 = {"path": "p.py", "line": 9, "pass": "x", "message": "m"}
        new, fixed = vet._diff_sets([a], [a, a2])
        assert new == [a2] and fixed == []
        new, fixed = vet._diff_sets([a, a2], [a])
        assert new == [] and len(fixed) == 1

    def test_diff_missing_baseline_is_exit_2(self, tmp_path):
        r = self._run("--files", os.path.join(FIXTURES, "jaxaudit_bad.py"),
                      "--diff", str(tmp_path / "nope.json"))
        assert r.returncode == 2, r.stdout + r.stderr
        assert "unusable baseline" in r.stderr

    def test_baseline_diff_roundtrip(self, tmp_path):
        """--baseline emits stable sorted JSON; --diff against that
        baseline reports {"new": [], "fixed": []} and exits 0; a finding
        absent from the baseline exits 1 as `new` (the cross-commit
        regression contract)."""
        fixtures = sorted(
            os.path.join(FIXTURES, f) for f in os.listdir(FIXTURES) if f.endswith(".py"))
        base = tmp_path / "base.json"
        r = self._run("--files", *fixtures, "--baseline", str(base))
        assert r.returncode == 0, r.stdout + r.stderr
        recorded = json.loads(base.read_text())
        assert recorded and recorded == sorted(
            recorded, key=lambda d: (d["path"], d["line"], d["pass"]))
        r = self._run("--files", *fixtures, "--diff", str(base))
        assert r.returncode == 0, r.stdout + r.stderr
        d = json.loads(r.stdout)
        assert d == {"new": [], "fixed": []}
        # an EMPTY baseline makes every corpus finding "new" -> exit 1
        empty = tmp_path / "empty.json"
        empty.write_text("[]")
        r = self._run("--files", *fixtures, "--diff", str(empty))
        assert r.returncode == 1
        d = json.loads(r.stdout)
        assert d["fixed"] == [] and len(d["new"]) == len(recorded)


# ------------------------------------------- dataflow engine: unit seeds

class TestDataflowEngine:
    @pytest.fixture(scope="class")
    def graph(self):
        from tidb_tpu.analysis.common import load_files, py_files

        return dataflow.graph_for(load_files(py_files("tidb_tpu")))

    def test_call_graph_resolves_dispatch_into_the_store(self, graph):
        fi = graph.funcs["tidb_tpu/distsql/dispatch.py::_run_one_task"]
        callees = {c.qname for c, _ in fi.callees}
        assert "tidb_tpu/store/store.py::TPUStore.coprocessor" in callees

    def test_request_path_cone_is_nontrivial(self, graph):
        reach = graph.reachable(graph.request_roots())
        assert "tidb_tpu/store/store.py::TPUStore.region_chunk" in reach
        assert "tidb_tpu/store/kv.py::MemKV.scan" in reach
        # the PD's control-plane scan is NOT on the request path: its
        # latest-version split-key read is legitimate there
        assert "tidb_tpu/pd/core.py::PlacementDriver._split_key" not in reach

    def test_start_ts_fact_reaches_the_kv_seam(self, graph):
        dataflow.TaintAnalysis(graph)
        fi = graph.funcs["tidb_tpu/store/store.py::TPUStore._scan_region_kvs"]
        assert dataflow.TS in fi.facts.get("start_ts", set())

    def test_escape_tracks_typed_errors_to_the_boundary(self, graph):
        dataflow.EscapeAnalysis(graph)
        b = graph.boundaries()[0]
        names = {t[1] for t in b.escapes if isinstance(t, tuple)}
        # the mapped dispatch errors DO reach the boundary (the mapping
        # is what keeps them out of the findings, not their absence)
        assert "RegionUnavailableError" in names or "CopInternalError" in names


# --------------------------------------------------- jax-audit: live view

class TestJaxAudit:
    def test_catalog_covers_every_builder_path(self):
        names = {n for n, _dag, _nb, _caps in jaxaudit.live_catalog()}
        assert names == {"selection", "hashagg", "streamagg", "topn", "hashjoin",
                         "radix_join", "partial_scalar_agg", "partial_hashagg",
                         "columnar_scan"}

    def test_mesh_variants_audited(self):
        """The mesh-tier shard_map programs are walked too: every catalog
        shape the dispatch planner would route to the mesh gets a
        mesh-{kind} trace through the jaxpr checks."""
        from tidb_tpu.distsql.planner import mesh_merge_kind

        kinds = {n: mesh_merge_kind(dag) for n, dag, _nb, _caps in jaxaudit.live_catalog()}
        assert kinds["partial_scalar_agg"] == "scalar"
        assert kinds["partial_hashagg"] == "group"
        assert kinds["topn"] == "topn"
        assert kinds["radix_join"] == "group"  # the radix join meshes too
        assert kinds["hashagg"] is None  # Complete mode stays off-mesh

    def test_live_catalog_is_clean(self):
        assert jaxaudit.run() == []

    def test_live_audit_fires_when_literals_are_baked(self, monkeypatch):
        """With the rule that makes a constant an operand switched off,
        every catalog program with a literal bakes it, and the
        two-literal build says so."""
        from tidb_tpu.expr.ir import Const

        monkeypatch.setattr(Const, "operand", lambda self: None)
        monkeypatch.setattr(jaxaudit, "_LIVE_MEMO", None)
        msgs = _messages(jaxaudit.run())
        baked = {m.split("'")[1] for m in msgs if "two different literal values" in m}
        assert baked == {"selection/single", "topn/single", "columnar_scan/single"}, msgs

    def test_vmap_axis_checker_fires_on_drift(self):
        class _A:
            def __init__(self, shape, dtype):
                self.shape, self.dtype = shape, dtype

        single = [_A((8,), "int64")]
        good = [_A((jaxaudit._VMAP_BATCH, 8), "int64")]
        assert jaxaudit._check_vmap_axis("x", single, good, ("f", 1)) == []
        dropped = [_A((8,), "int64")]  # region axis lost
        retyped = [_A((jaxaudit._VMAP_BATCH, 8), "int32")]
        assert jaxaudit._check_vmap_axis("x", single, dropped, ("f", 1))
        assert jaxaudit._check_vmap_axis("x", single, retyped, ("f", 1))


# ----------------------------------------------------- result cache

class TestVetCache:
    def test_roundtrip_and_invalidation(self, tmp_path, monkeypatch):
        from tidb_tpu.analysis.common import Finding
        from tidb_tpu.analysis.vetcache import VetCache

        monkeypatch.setenv("TIDB_TPU_VET_CACHE", str(tmp_path / "c.json"))
        src = tmp_path / "m.py"
        src.write_text("x = 1\n")
        sf = SourceFile.load(str(src), repo=str(tmp_path))
        c = VetCache()
        key = VetCache.file_key("p", "sha1", sf)
        c.put(key, [Finding("m.py", 1, "p", "msg")])
        c.save()
        c2 = VetCache()
        hit = c2.get(key)
        assert hit and hit[0].render() == "m.py:1: [p] msg"
        # editing the file changes (mtime, sha) -> a different key: miss
        src.write_text("x = 2\n")
        sf2 = SourceFile.load(str(src), repo=str(tmp_path))
        assert VetCache.file_key("p", "sha1", sf2) != key
        assert c2.get(VetCache.file_key("p", "sha1", sf2)) is None

    def test_run_all_cold_equals_warm(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TIDB_TPU_VET_CACHE", str(tmp_path / "c.json"))
        cold = analysis.run_all()
        warm = analysis.run_all()
        assert [f.render() for f in cold] == [f.render() for f in warm] == []


# ------------------------------------------------- lockwatch: unit seeds

class _Shared:
    def __init__(self):
        self._mu = threading.Lock()
        self.val = 0


class TestLockwatch:
    def test_seeded_lock_order_cycle_is_reported(self):
        with lockwatch.watching(guard_tree=False) as w:
            a = threading.Lock()
            b = threading.Lock()
            assert isinstance(a, lockwatch.WatchedLock)  # repo frame: wrapped
            with a:
                with b:
                    pass
            with b:
                with a:  # the ABBA inversion
                    pass
        rep = w.report()
        assert rep["cycles"], rep["edges"]
        cyc = rep["cycles"][0]
        assert any("test_vet.py" in site for site in cyc)

    def test_consistent_order_reports_no_cycle(self):
        with lockwatch.watching(guard_tree=False) as w:
            a = threading.Lock()
            b = threading.Lock()
            for _ in range(3):
                with a:
                    with b:
                        pass
        assert w.report()["cycles"] == []

    def test_seeded_unguarded_write_is_reported(self):
        with lockwatch.watching(guard_tree=False) as w:
            obj = _Shared()
            w.guard_class(_Shared, {"val": "_mu"})
            obj.val = 1  # first (exclusive) thread: exempt

            def racy():
                obj.val = 2  # second thread, guard not held

            t = threading.Thread(target=racy)
            t.start()
            t.join()
            assert w.violations, "unguarded cross-thread write not reported"
            v = w.violations[0]
            assert v.attr == "val" and v.guard == "_mu" and v.mode == "write"

            n = len(w.violations)

            def disciplined():
                with obj._mu:
                    obj.val = 3

            t = threading.Thread(target=disciplined)
            t.start()
            t.join()
            assert len(w.violations) == n  # guarded access stays quiet

    def test_rlock_reentry_adds_no_edge(self):
        with lockwatch.watching(guard_tree=False) as w:
            r = threading.RLock()
            with r:
                with r:
                    pass
        assert w.report()["edges"] == []

    def test_stdlib_locks_stay_real(self):
        with lockwatch.watching(guard_tree=False):
            import queue

            q = queue.Queue()  # stdlib frames create its internal locks
            q.put(1)
            assert q.get() == 1
            assert not isinstance(q.mutex, lockwatch.WatchedLock)


# ------------------------------------ lockwatch over the tier-1 workloads

def test_chaos_storm_under_lockwatch():
    """ISSUE 7 acceptance: the PR-6 seeded chaos storm — store outage,
    busy storm, heartbeat blackout, not-leader flaps, operator timeouts —
    runs under the runtime detector with ZERO lock-order cycles and ZERO
    unguarded annotated accesses, while keeping its own invariants."""
    from chaos import run_chaos

    with lockwatch.watching() as w:
        report = run_chaos(seed=11, statements=40)
    rep = w.report()
    assert rep["cycles"] == [], rep["cycles"]
    assert rep["violations"] == [], "\n".join(rep["violations"])
    assert report["wrong_results"] == [] and report["untyped_errors"] == []
    # the detector actually observed the engine's locking (not a no-op run)
    assert rep["edges"], "lockwatch saw no lock nesting at all"


def test_replication_catchup_under_lockwatch():
    """ISSUE 9 satellite: the replication CATCH-UP path under the runtime
    detector — leader transfers, the resolved-ts catch-up driver and a
    follower-read dispatch pool racing while writes land and the
    apply-lag failpoint wedges/unwedges followers. Zero lock-order
    cycles, zero unguarded annotated accesses, scans never lose rows,
    and once writers stop and the wedge lifts, catch-up drains every
    follower's safe_ts lag to zero."""
    from tidb_tpu.codec import tablecodec
    from tidb_tpu.distsql.dispatch import KVRequest, full_table_ranges, select
    from tidb_tpu.exec.dag import ColumnInfo, DAGRequest, TableScan
    from tidb_tpu.types import Datum, new_longlong
    from tidb_tpu.util import failpoint

    TID, rows, regions = 37, 120, 6
    with lockwatch.watching() as w:
        from tidb_tpu.store import TPUStore

        store = TPUStore()
        for h in range(rows):
            store.put_row(TID, h, [1], [Datum.i64(h)], ts=10)
        for i in range(1, regions):
            store.cluster.split(tablecodec.encode_row_key(TID, i * rows // regions))
        store.cluster.set_stores(4)
        store.cluster.scatter()
        dag = DAGRequest((TableScan(TID, (ColumnInfo(1, new_longlong()),)),),
                         output_offsets=(0,))
        stop = threading.Event()
        errors: list = []
        counts: list = []

        def scanner():
            # snapshot at 50: the seed rows (ts=10) are visible, the
            # writer's versions (TSO >= 100) are not — every scan must
            # return exactly the seed rows, through transfers, wedged
            # followers and DataIsNotReady fallbacks
            while not stop.is_set():
                try:
                    res = select(store, KVRequest(
                        dag, full_table_ranges(TID), 50, replica_read="follower"))
                    counts.append(sum(c.num_rows() for c in res.chunks))
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        def writer():
            h = rows
            while not stop.is_set():
                store.put_row(TID, h, [1], [Datum.i64(h)], ts=store.next_ts())
                h += 1

        def transferrer():
            k = 0
            while not stop.is_set():
                for r in store.cluster.regions():
                    folls = store.cluster.followers_of(r.region_id)
                    if folls:
                        store.cluster.transfer_leader(
                            r.region_id, folls[k % len(folls)])
                k += 1

        def catcher_up():
            while not stop.is_set():
                store.replication.catch_up()

        threads = [threading.Thread(target=t, daemon=True)
                   for t in (scanner, scanner, writer, transferrer, catcher_up)]
        for t in threads:
            t.start()
        import time

        # phase 1: wedge one follower's apply loop (lag accumulates)
        with failpoint.enabled("replica/apply-lag", {1}):
            time.sleep(0.6)
        # phase 2: wedge lifted — the catch-up thread drains the lag
        time.sleep(0.6)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        # quiesced: a few explicit catch-up rounds must zero every lag
        for _ in range(5):
            store.replication.catch_up()
        lags = store.replication.lag_view()
    rep = w.report()
    assert rep["cycles"] == [], rep["cycles"]
    assert rep["violations"] == [], "\n".join(rep["violations"])
    assert not errors, errors
    assert counts and all(c == rows for c in counts)
    assert all(v == 0 for v in lags.values()), lags
    assert rep["edges"], "lockwatch saw no lock nesting at all"


def test_pd_concurrent_dispatch_under_lockwatch():
    """PD tick thread vs dispatch pool under the detector: splits, moves
    and failpoint storms while scans run — no cycles, no violations."""
    from tidb_tpu.codec import tablecodec
    from tidb_tpu.distsql.dispatch import KVRequest, full_table_ranges, select
    from tidb_tpu.exec.dag import ColumnInfo, DAGRequest, TableScan
    from tidb_tpu.types import Datum, new_longlong
    from tidb_tpu.util import failpoint

    TID, rows = 31, 160
    with lockwatch.watching() as w:
        from tidb_tpu.store import TPUStore

        store = TPUStore()
        for h in range(rows):
            store.put_row(TID, h, [1], [Datum.i64(h)], ts=10)
        for i in range(1, 8):
            store.cluster.split(tablecodec.encode_row_key(TID, i * rows // 8))
        store.cluster.set_stores(4)
        store.cluster.scatter()
        dag = DAGRequest((TableScan(TID, (ColumnInfo(1, new_longlong()),)),),
                         output_offsets=(0,))
        stop = threading.Event()
        errors: list = []
        counts: list = []

        def scanner():
            while not stop.is_set():
                try:
                    res = select(store, KVRequest(dag, full_table_ranges(TID), 100))
                    counts.append(sum(c.num_rows() for c in res.chunks))
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        threads = [threading.Thread(target=scanner, daemon=True) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            with failpoint.enabled("pd/heartbeat-lost"), \
                 failpoint.enabled("pd/operator-timeout"):
                for _ in range(4):
                    store.pd.tick()
            for _ in range(6):
                store.pd.tick()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
    assert errors == []
    assert counts and all(c == rows for c in counts)
    rep = w.report()
    assert rep["cycles"] == [], rep["cycles"]
    assert rep["violations"] == [], "\n".join(rep["violations"])
    assert rep["edges"]
