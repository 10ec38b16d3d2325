"""Top SQL (ISSUE 17; ref: pkg/util/topsql + ng-monitoring): windowed
per-digest CPU+device attribution threaded through every execution
layer, and the admission gate's measured-cost mode it feeds.

Covers: the one-digest join across slow log / statements_summary /
tidb_top_sql / plan cache (normalize_sql is fallback-only), exact
attribution conservation across the single / vmapped-batch / mesh cop
tiers (per-lane row-weighted splits sum exactly; cop-cache hits lose
nothing), window top-K + "(others)" fold conservation, EWMA cost-class
re-learning, cost-classed shedding (heavy sheds typed 9003 while
point-gets keep flowing), byte-consistency of the four surfaces
(collector view == information_schema == HTTP API == Prometheus
counters), the PD tick's topsql.report span, scrape_check on the new
metric families, and a lockwatch storm over rotation vs sessions vs
the PD tick."""

import contextlib
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from tidb_tpu import topsql
from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql import KVRequest, full_table_ranges, select
from tidb_tpu.exec import Aggregation, ColumnInfo, DAGRequest, Selection, TableScan
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.sql.session import Session, SQLError
from tidb_tpu.store import TPUStore
from tidb_tpu.topsql import (
    CLASS_WEIGHTS,
    COLLECTOR,
    OTHERS_DIGEST,
    ResourceTag,
    TopSQLCollector,
    split_by_rows,
)
from tidb_tpu.types import Datum, new_longlong
from tidb_tpu.util import metrics, tracing
from tidb_tpu.util.stmtlog import normalize_sql

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

BOOL = new_longlong(notnull=True)
TID = 97
FT = new_longlong()


def fill_store(n=200, regions=8):
    store = TPUStore()
    for h in range(n):
        store.put_row(TID, h, [1], [Datum.i64(h * 3)], ts=10)
    for i in range(1, regions):
        store.cluster.split(tablecodec.encode_row_key(TID, i * n // regions))
    return store


def scan_dag():
    scan = TableScan(TID, (ColumnInfo(1, FT),))
    return DAGRequest((scan,), output_offsets=(0,))


def agg_dag():
    scan = TableScan(TID, (ColumnInfo(1, FT),))
    sel = Selection((func("lt", BOOL, col(0, FT), lit(300, new_longlong())),))
    agg = Aggregation(group_by=(), aggs=(AggDesc("count", ()),), partial=True)
    return DAGRequest((scan, sel, agg), output_offsets=(0,))


def kvreq(dag, ts, **kw):
    return KVRequest(dag, full_table_ranges(TID), start_ts=ts, **kw)


def snap(digest, cpu=0, dev=0, compile_ns=0, backoff=0.0, queue=0.0,
         byt=0, cop_hits=0, plan_digest="", sample=""):
    """A finished-tag snapshot, shaped like ResourceTag.snapshot()."""
    return {
        "sql_digest": digest, "plan_digest": plan_digest, "sample_sql": sample,
        "cpu_ns": cpu, "device_ns": dev, "compile_ns": compile_ns,
        "backoff_ms": backoff, "queue_ms": queue, "bytes_to_device": byt,
        "cop_cache_hits": cop_hits,
    }


# ------------------------------------------------------- exact lane split


def test_split_by_rows_exact():
    assert split_by_rows(0, []) == []
    assert split_by_rows(100, [1]) == [100]
    # always sums exactly, proportional, deterministic
    s = split_by_rows(1000, [1, 2, 7])
    assert sum(s) == 1000 and s[2] > s[1] > s[0]
    s = split_by_rows(7, [3, 3, 3])
    assert sum(s) == 7
    # all-zero rows degrade to equal split, still exact
    s = split_by_rows(10, [0, 0, 0])
    assert sum(s) == 10 and max(s) - min(s) <= 1
    # negative guard + skew
    s = split_by_rows(12345, [-1, 0, 1, 10**6])
    assert sum(s) == 12345 and s[3] >= 12343


# ------------------------------------------------------ digest unification


class TestDigestUnification:
    def test_four_surfaces_share_one_digest(self):
        """Slow log, statements_summary, tidb_top_sql and the plan cache
        all key the SAME statement by ONE digest — the plan-cache probe's
        literal-masked digest from its single lexer pass."""
        COLLECTOR.reset()
        s = Session()
        s.execute("create table t (a bigint primary key, b bigint)")
        s.execute("insert into t values (1, 10), (2, 20)")
        s.execute("set tidb_slow_log_threshold = 0")
        s.execute("select b from t where a = 1")
        s.execute("select b from t where a = 2")  # plan-cache hit
        s.execute("set tidb_slow_log_threshold = 300")
        digest = normalize_sql("select b from t where a = 1")[1]

        slow = s.execute(
            f"select digest from information_schema.slow_query where digest = '{digest}'"
        ).values()
        assert slow, "slow log missed the digest"
        summ = s.execute(
            "select digest, exec_count from information_schema.statements_summary "
            f"where digest = '{digest}'"
        ).values()
        assert summ and summ[0][1] == 2
        top = s.execute(
            "select digest, exec_count, plan_cache_hits from "
            f"information_schema.tidb_top_sql where digest = '{digest}'"
        ).values()
        assert top and top[0][1] == 2
        # the plan cache joined on the same digest: the second execution
        # was a hit, and Top SQL saw it as one
        assert top[0][2] >= 1

    def test_normalize_sql_is_fallback_only(self, monkeypatch):
        """A probed statement never re-lexes: the probe's digest rides
        from the plan cache through the stmt log and Top SQL, so
        normalize_sql is not called on the hot path."""
        from tidb_tpu.util import stmtlog as sl

        s = Session()
        s.execute("create table t (a bigint primary key)")
        s.execute("insert into t values (1)")
        s.execute("select a from t where a = 1")  # warm every cache

        calls = []
        real = sl.normalize_sql

        def counting(sql):
            calls.append(sql)
            return real(sql)

        monkeypatch.setattr(sl, "normalize_sql", counting)
        s.execute("select a from t where a = 1")
        assert calls == [], f"hot path re-lexed: {calls}"


# ------------------------------------------------ attribution conservation


@contextlib.contextmanager
def statement(tag):
    """What a `Session` does around a statement: the tag ambient, and at
    its end what the statement's own thread charged handed to the tag (the
    dispatch executor's tasks hand theirs over themselves)."""
    mark = tracing.clock_mark()
    with topsql.adopt(tag):
        yield
    tag.add_host(tracing.clock_since(mark))


class TestConservation:
    def test_tiers_conserve_device_time(self):
        """sum(per-digest device_ns) == sum(launch waits), exactly,
        across the per-region, vmapped-batch and mesh tiers: both sides
        are the state clock's `exec.wait`, the tag's handed over by the
        executor's workers task by task and by the statement's own thread
        at its end, the ledger's noted launch by launch.  Per-lane
        ExecSummary shares sum to each cop request's elapsed, which holds
        the wait."""
        COLLECTOR.reset()
        store = fill_store(n=200, regions=8)
        tag = ResourceTag("tier-test")
        with statement(tag):
            select(store, kvreq(scan_dag(), 100, concurrency=2, mesh=False))
            store.evict_caches()
            res_b = select(store, kvreq(scan_dag(), 101, batch_cop=True, mesh=False))
            store.evict_caches()
            select(store, kvreq(agg_dag(), 102))  # planner default: mesh tier
        assert tag.device_ns > 0
        assert tag.device_ns == COLLECTOR.launch_device_ns
        assert tag.compile_ns > 0 and tag.bytes_to_device > 0
        assert tag.host_ns["exec.wait"] == tag.device_ns
        assert all(task[0].time_processed_ns > 0 for task in res_b.exec_summaries)
        # re-run the batched tier alone under a fresh tag: every lane of
        # the launch carries its row-weighted share of the request's
        # elapsed time, of which the wait for the device is a part
        store.evict_caches()
        ledger = COLLECTOR.launch_device_ns
        tag2 = ResourceTag("lane-sum")
        with statement(tag2):
            res2 = select(store, kvreq(scan_dag(), 103, batch_cop=True, mesh=False))
        lane_total = sum(task[0].time_processed_ns for task in res2.exec_summaries)
        assert lane_total > tag2.device_ns == COLLECTOR.launch_device_ns - ledger > 0
        # one store: the batch ran on the calling thread, no worker's state and no wait for one
        assert {"exec.launch", "exec.wait", "exec.readback"} <= set(tag2.host_ns)
        assert not {"distsql.task", "distsql.wait_tasks"} & set(tag2.host_ns) and tag2.pool_cpu_ns == 0

    def test_a_several_store_batch_hands_the_workers_states_to_the_tag(self):
        """Store groups fanned out to the dispatch executor: each worker's
        task hands its states to the statement's tag, the calling thread's
        wait is not among them, and the ledger still balances."""
        COLLECTOR.reset()
        store = fill_store(n=200, regions=8)
        store.cluster.set_stores(2)
        store.cluster.scatter()
        tag = ResourceTag("two-stores")
        with topsql.adopt(tag):
            res = select(store, kvreq(scan_dag(), 104, batch_cop=True, mesh=False))
        assert res.batch_stats["regions"] == 8 and res.batch_stats["batches"] == 2
        assert tag.device_ns == COLLECTOR.launch_device_ns > 0
        # the workers' states, and nothing of the calling thread's
        assert {"distsql.task", "exec.launch", "exec.wait", "exec.readback"} <= set(tag.host_ns)
        assert "distsql.wait_tasks" not in tag.host_ns and tag.pool_cpu_ns >= 0

    def test_cop_cache_hits_lose_nothing(self):
        """A fully cached re-read does zero device work: the tag shows
        the hit count instead of silently attributing nothing, and the
        conservation ledger is untouched."""
        COLLECTOR.reset()
        store = fill_store(n=120, regions=6)
        select(store, kvreq(scan_dag(), 100, concurrency=2, mesh=False))  # untagged populate
        assert COLLECTOR.launch_device_ns == 0  # no ambient tag, no ledger
        tag = ResourceTag("cached")
        l0 = metrics.PROGRAM_LAUNCHES.value
        with topsql.adopt(tag):
            select(store, kvreq(scan_dag(), 101, concurrency=2, mesh=False))
        assert metrics.PROGRAM_LAUNCHES.value == l0  # served from cop cache
        assert tag.device_ns == 0 and tag.cop_cache_hits == 6
        assert COLLECTOR.launch_device_ns == 0

    def test_untagged_sinks_are_free_noops(self):
        topsql.record_device(compile_ns=1, bytes_to_device=123)
        topsql.note_launch(123)
        topsql.record_device_share(123)
        topsql.record_backoff(1.0)
        topsql.record_queue_wait(1.0)
        topsql.record_cop_cache_hit()  # no ambient tag: all no-ops


# ----------------------------------------------------- windows + the fold


class TestReporterWindows:
    def test_topk_union_and_others_fold_conserve(self):
        """A sealed window keeps the union of top-K digests BY EACH
        metric and folds the rest into (others) — window totals stay
        conservation-exact."""
        c = TopSQLCollector(window_s=1000.0, top_k=1)
        c.record_statement(snap("cpu-hog", cpu=1000))
        c.record_statement(snap("backoff-hog", cpu=1, backoff=500.0))
        c.record_statement(snap("dev-hog", dev=900))
        c.record_statement(snap("nobody-1", cpu=2))
        c.record_statement(snap("nobody-2", cpu=3))
        assert c.rotate(force=True) == 1
        (w,) = c.windows_view()
        kept = {d["digest"] for d in w["digests"]}
        # top-1 by cpu, by device and by backoff all survive independently
        assert {"cpu-hog", "backoff-hog", "dev-hog"} <= kept
        assert "nobody-1" not in kept and "nobody-2" not in kept
        assert w["others"]["digest"] == OTHERS_DIGEST
        assert w["others"]["exec_count"] == 2
        total_cpu = sum(d["cpu_ns"] for d in w["digests"]) + w["others"]["cpu_ns"]
        assert total_cpu == c.totals["cpu_ns"] == 1006

    def test_ring_is_bounded_and_ordered(self):
        clock = [0.0]
        c = TopSQLCollector(window_s=1.0, ring=3, now_fn=lambda: clock[0])
        for i in range(6):
            if i:
                clock[0] += 1.5  # every statement lands in its own window
            c.record_statement(snap(f"d{i}", cpu=10))
        views = c.windows_view()
        sealed = [w for w in views if not w["live"]]
        assert len(sealed) == 3  # ring bound ate the oldest
        assert [w["start"] for w in sealed] == sorted(w["start"] for w in sealed)
        live = [w for w in views if w["live"]]
        assert len(live) == 1 and live[0]["digests"][0]["digest"] == "d5"

    def test_sysvar_bridges(self):
        s = Session()
        try:
            s.execute("set tidb_top_sql_max_statement_count = 7")
            assert COLLECTOR.top_k == 7
            s.execute("set tidb_enable_top_sql = OFF")
            assert not COLLECTOR.enabled
            COLLECTOR.reset()
            s.execute("select 1")
            assert COLLECTOR.windows_view() == []  # nothing recorded while off
        finally:
            s.execute("set tidb_enable_top_sql = ON")
            s.execute("set tidb_top_sql_max_statement_count = 30")
        assert COLLECTOR.enabled and COLLECTOR.top_k == 30

    def test_pd_tick_runs_the_reporter(self):
        """The PD tick owns the rotation clock: a topsql.report child
        span under pd.tick, and a due live window actually seals."""
        c = COLLECTOR
        c.reset()
        c.configure(window_s=0.001)
        try:
            c.record_statement(snap("tick-digest", cpu=5))
            time.sleep(0.005)
            store = fill_store(n=20, regions=2)
            store.pd.tick()
            root = store.pd.last_tick_root
            assert root is not None
            names = {ch.name for ch in root.children}
            assert "topsql.report" in names
            sealed = [w for w in c.windows_view() if not w["live"]]
            assert sealed and sealed[0]["digests"][0]["digest"] == "tick-digest"
        finally:
            c.configure(window_s=1.0)


# ------------------------------------------------------------ cost classes


class TestCostClasses:
    def test_ewma_classifies_and_relearns(self):
        """Classes are measured, never guessed — and re-learned: a digest
        whose plan changes migrates as soon as the EWMA crosses."""
        c = TopSQLCollector()
        assert c.cost_class("never-seen") == "small"  # DEFAULT_CLASS
        for _ in range(3):
            c.record_statement(snap("d", cpu=80_000_000, dev=80_000_000))
        assert c.cost_class("d") == "heavy"
        # the plan improved: cheap executions walk the EWMA back down
        for _ in range(12):
            c.record_statement(snap("d", cpu=100_000))
        assert c.cost_class("d") == "point"
        assert c.weight("d") == CLASS_WEIGHTS["point"] == 1

    def test_heavy_sheds_while_point_flows(self):
        from tidb_tpu.server.admission import AdmissionGate, AdmissionShed

        g = AdmissionGate(max_inflight=4, session_queue=0, queue_wait_ms=5.0,
                          cost_classed=True,
                          classifier=lambda d: "heavy" if d == "H" else "point")
        held = g.admit("h1", digest="H")  # heavy lane: 4 // 4 = 1 slot
        try:
            with pytest.raises(AdmissionShed) as ei:
                g.admit("h2", digest="H")
            assert ei.value.where in ("queue_full", "queue_timeout")
            # the full point-get budget still flows beside the wedged lane
            pts = [g.admit(f"p{i}", digest="P") for i in range(4)]
            v = g.view()
            assert v["by_class"] == {"heavy": 1, "point": 4}
            assert v["weighted_inflight"] == 8
            for t in pts:
                t.__exit__(None, None, None)
        finally:
            held.__exit__(None, None, None)
        assert g.view()["by_class"] == {}

    def test_session_shed_is_typed_9003(self):
        """End to end: measured-heavy digest sheds at a saturated gate as
        SQLError 9003 while a measured-point statement still runs."""
        COLLECTOR.reset()
        s = Session()
        s.execute("create table t (a bigint primary key, b bigint)")
        s.execute("insert into t values (1, 10), (2, 20)")
        heavy_sql = "select sum(b) from t where b > 0"
        point_sql = "select b from t where a = 1"
        heavy_d = normalize_sql(heavy_sql)[1]
        point_d = normalize_sql(point_sql)[1]
        for _ in range(3):  # train the EWMAs: measured, not guessed
            COLLECTOR.record_statement(snap(heavy_d, cpu=200_000_000))
            COLLECTOR.record_statement(snap(point_d, cpu=50_000))
        assert COLLECTOR.cost_class(heavy_d) == "heavy"
        assert COLLECTOR.cost_class(point_d) == "point"

        gate = s.store.admission
        gate.configure(max_inflight=4, session_queue=0, queue_wait_ms=2.0,
                       cost_classed=True)
        held = gate.admit("wedge", digest=heavy_d)  # heavy lane full (cap 1)
        try:
            with pytest.raises(SQLError) as ei:
                s.execute(heavy_sql)
            assert ei.value.code == 9003
            assert s.execute(point_sql).values() == [[10]]
        finally:
            held.__exit__(None, None, None)
            gate.configure(max_inflight=0, session_queue=4,
                           queue_wait_ms=50.0, cost_classed=False)

    def test_queue_wait_attributed_to_the_waiter(self):
        from tidb_tpu.server.admission import AdmissionGate

        g = AdmissionGate(max_inflight=1, session_queue=2, queue_wait_ms=200.0)
        held = g.admit("holder")
        tag = ResourceTag("waiter")
        got = []

        def waiter():
            with topsql.adopt(tag):
                with g.admit("w"):
                    got.append(True)

        th = threading.Thread(target=waiter, daemon=True)
        th.start()
        time.sleep(0.03)
        held.__exit__(None, None, None)
        th.join(timeout=30)
        assert got and tag.queue_ms > 0


# ------------------------------------------------- surfaces stay in sync


def test_surfaces_byte_consistent():
    """One serializer, four surfaces: the collector's windows_view, the
    information_schema memtable, the HTTP API and the Prometheus counters
    all show THE SAME numbers."""
    COLLECTOR.reset()
    cpu0 = metrics.TOPSQL_CPU_NS.value
    dev0 = metrics.TOPSQL_DEVICE_NS.value
    n0 = metrics.TOPSQL_RECORDS.value
    s = Session()
    s.execute("create table t (a bigint primary key, b bigint)")
    s.execute("insert into t values " + ",".join(f"({i},{i})" for i in range(64)))
    for i in range(4):
        s.execute(f"select sum(b) from t where a > {i}")
    s.execute("set tidb_enable_top_sql = OFF")  # freeze: reads don't self-record
    COLLECTOR.rotate(force=True)
    try:
        view = COLLECTOR.windows_view()
        assert view and all(not w["live"] for w in view)

        def total(win_list, key):
            return sum(
                sum(d[key] for d in w["digests"])
                + (w["others"][key] if w["others"] else 0)
                for w in win_list
            )

        # collector totals == window sums == prometheus counter deltas
        assert total(view, "cpu_ns") == COLLECTOR.totals["cpu_ns"] == \
            metrics.TOPSQL_CPU_NS.value - cpu0
        assert total(view, "device_ns") == COLLECTOR.totals["device_ns"] == \
            metrics.TOPSQL_DEVICE_NS.value - dev0
        assert COLLECTOR.totals["exec_count"] == metrics.TOPSQL_RECORDS.value - n0
        # ... == the conservation ledger (every launch was tagged)
        assert COLLECTOR.totals["device_ns"] == COLLECTOR.launch_device_ns

        # information_schema renders the same rows
        rows = s.execute(
            "select digest, exec_count, cpu_ns, device_ns "
            "from information_schema.tidb_top_sql"
        ).values()
        by_digest = {}
        for dg, ec, cpu, dev in rows:
            acc = by_digest.setdefault(dg, [0, 0, 0])
            acc[0] += ec
            acc[1] += cpu
            acc[2] += dev
        want = {}
        for w in view:
            for d in w["digests"] + ([w["others"]] if w["others"] else []):
                acc = want.setdefault(d["digest"], [0, 0, 0])
                acc[0] += d["exec_count"]
                acc[1] += d["cpu_ns"]
                acc[2] += d["device_ns"]
        assert by_digest == want

        # the HTTP API serves the very same serializer output
        from tidb_tpu.server.http_api import StatusServer

        srv = StatusServer(s).start_background()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            api = json.loads(urllib.request.urlopen(base + "/topsql/api/v1/windows").read())
            assert api == json.loads(json.dumps(view, default=str))
            dg = view[-1]["digests"][0]["digest"]
            one = json.loads(urllib.request.urlopen(
                base + f"/topsql/api/v1/digests/{dg}").read())
            assert one["digest"] == dg and one["windows"]
            assert one["cost_class"] in ("point", "small", "scan", "heavy")
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/topsql/api/v1/digests/absent")
        finally:
            srv.close()
    finally:
        s.execute("set tidb_enable_top_sql = ON")


def test_statements_summary_enriched_columns():
    COLLECTOR.reset()
    s = Session()
    s.execute("create table t (a bigint primary key, b bigint)")
    s.execute("insert into t values " + ",".join(f"({i},{i})" for i in range(300)))
    s.execute("select sum(b) from t where a >= 0")
    digest = normalize_sql("select sum(b) from t where a >= 0")[1]
    rows = s.execute(
        "select avg_device_ns, max_device_ns, avg_compile_ns, cost_class "
        f"from information_schema.statements_summary where digest = '{digest}'"
    ).values()
    assert rows
    avg_dev, max_dev, avg_comp, cls = rows[0]
    assert avg_dev > 0 and max_dev >= avg_dev and avg_comp > 0
    assert cls in ("point", "small", "scan", "heavy")


def test_metric_families_pass_scrape_check():
    COLLECTOR.reset()
    s = Session()
    s.execute("create table t (a bigint primary key)")
    s.execute("insert into t values (1)")
    s.execute("select a from t where a = 1")
    COLLECTOR.rotate(force=True)
    text = metrics.REGISTRY.dump()
    for family in (
        "tidb_tpu_topsql_records_total",
        "tidb_tpu_topsql_cpu_ns_total",
        "tidb_tpu_topsql_device_ns_total",
        "tidb_tpu_topsql_compile_ns_total",
        "tidb_tpu_topsql_backoff_ms_total",
        "tidb_tpu_topsql_queue_ms_total",
        "tidb_tpu_topsql_launch_device_ns_total",
        "tidb_tpu_topsql_windows_sealed_total",
        "tidb_tpu_topsql_live_digests",
        "tidb_tpu_topsql_class_admissions_total",
    ):
        assert f"# TYPE {family}" in text, family
    from scrape_check import validate

    assert validate(text) == []


# ------------------------------------------------------- lockwatch storm


def test_topsql_lockwatch_storm():
    """Window rotation + 8 recording sessions + the PD tick's reporter
    phase, all racing under the runtime lockset detector: zero lock-order
    cycles, zero unguarded annotated accesses — the collector and tag
    locks really are leaves."""
    from tidb_tpu.analysis import lockwatch

    COLLECTOR.reset()
    with lockwatch.watching() as w:
        src = Session()
        src.execute("create table t (a bigint primary key, b bigint)")
        src.execute("insert into t values " + ",".join(
            f"({i},{i * 10})" for i in range(32)))
        gate = src.store.admission
        gate.configure(max_inflight=6, cost_classed=True)
        stop = threading.Event()
        errors: list = []

        def runner(seed):
            sess = Session(store=src.store, catalog=src.catalog)
            i = seed
            while not stop.is_set():
                try:
                    sess.execute(f"select b from t where a = {i % 32}")
                    sess.execute(f"select sum(b) from t where a > {i % 8}")
                    i += 1
                except SQLError:
                    pass
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        def rotator():
            while not stop.is_set():
                try:
                    COLLECTOR.rotate(force=True)
                    COLLECTOR.windows_view()
                    time.sleep(0.005)
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        def ticker():
            pd = getattr(src.store, "pd", None)
            while not stop.is_set():
                try:
                    if pd is not None:
                        pd.tick()
                    time.sleep(0.01)
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        threads = [threading.Thread(target=runner, args=(i * 5,), daemon=True)
                   for i in range(8)]
        threads.append(threading.Thread(target=rotator, daemon=True))
        threads.append(threading.Thread(target=ticker, daemon=True))
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        gate.configure(max_inflight=0, cost_classed=False)
    rep = w.report()
    assert rep["cycles"] == [], rep["cycles"]
    assert rep["violations"] == [], "\n".join(rep["violations"])
    assert not errors, errors
    assert metrics.TOPSQL_WINDOWS_SEALED.value > 0


def test_chaos_oracle_clean_with_cost_classed_gate():
    """ISSUE 17 acceptance: the answer-correctness chaos storm stays
    clean with Top SQL attribution on and the admission gate in
    measured-cost mode — classes learned live under faults, every shed
    typed 9003 (already in the storm's retryable set), zero wrong
    results, zero untyped errors."""
    from chaos import run_chaos

    topsql.COLLECTOR.reset()
    report = run_chaos(seed=13, statements=40, admission_flicker=0.1,
                       cost_classed=True)
    assert report["wrong_results"] == []
    assert report["untyped_errors"] == []
    assert report["breakers_all_closed"], report["breakers"]
    # the flicker-forced sheds surfaced typed, and the storm's statements
    # actually flowed through the collector (classes were live, not idle)
    assert report["errors_by_code"].get(9003, 0) >= 1
    assert topsql.COLLECTOR.totals["exec_count"] > 0
