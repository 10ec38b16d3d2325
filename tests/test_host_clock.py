"""The host-state clock (util/tracing.py): every nanosecond of a wire
command is charged, wall and CPU, to exactly one named state, on plain
statements as on traced ones; /metrics' counters, Top SQL and TRACE read
that one clock.  Identities and shapes only: no timing thresholds."""

import json
import sys
import threading
import time

import pytest

from tidb_tpu import topsql
from tidb_tpu.sql.session import Session
from tidb_tpu.topsql import COLLECTOR
from tidb_tpu.util import metrics, tracing

WALL = dict(tracing.HOST_STATES)
OTHERS = {"handle": metrics.SERVER_HANDLE_NS, "cpu": metrics.SERVER_CPU_NS, "pool": metrics.HOST_POOL_NS,
          "pool_cpu": metrics.HOST_POOL_CPU_NS, "commands": metrics.SERVER_COMMANDS}


class Charged:
    """What the clock's counters moved by around a block: `wall` by state
    (those that moved), and SERVER_HANDLE_NS, SERVER_CPU_NS, HOST_POOL_NS,
    HOST_POOL_CPU_NS, SERVER_COMMANDS."""

    def __enter__(self):
        self._w = {s: c.value for s, c in WALL.items()}
        self._o = {k: c.value for k, c in OTHERS.items()}
        return self

    def __exit__(self, *exc):
        self.wall = {s: c.value - self._w[s] for s, c in WALL.items() if c.value != self._w[s]}
        for k, c in OTHERS.items():
            setattr(self, k, c.value - self._o[k])


def handled(n: int, since: int) -> None:
    """The server books a command, and flushes its clock, once the last
    reply byte is out, which the client may see first."""
    deadline = time.monotonic() + 10
    while metrics.SERVER_COMMANDS.value - since < n and time.monotonic() < deadline:
        time.sleep(0.002)
    assert metrics.SERVER_COMMANDS.value - since == n


def ask(client, sql: str):
    """One command, answered and booked."""
    start = metrics.SERVER_COMMANDS.value
    out = client.query(sql)
    handled(1, start)
    return out


def find(node, name) -> list:
    out = [node] if node["name"] == name else []
    for c in node.get("children", []):
        out.extend(find(c, name))
    return out


def traced(sess, sql) -> dict:
    return json.loads(sess.execute(f"TRACE FORMAT='json' {sql}").values()[0][0])


@pytest.fixture()
def sess():
    s = Session()
    s.execute("CREATE TABLE hc (id BIGINT PRIMARY KEY, k BIGINT, c CHAR(20))")
    s.execute("INSERT INTO hc VALUES " + ",".join(f"({i},{i * 7 % 100},'c{i % 13:03d}')" for i in range(1, 301)))
    return s


@pytest.fixture()
def served(monkeypatch):
    """A server, a client on it, and the commands booked before the test's own."""
    from tidb_tpu.server import MiniClient, MySQLServer

    monkeypatch.setattr(tracing, "CPU_READ_NS", 0)   # every command's close reads the CPU clock
    srv = MySQLServer(port=0)
    srv.start_background()
    client = MiniClient(srv.host, srv.port, timeout=300)
    ask(client, "CREATE TABLE hc (id BIGINT PRIMARY KEY, k BIGINT, c CHAR(20))")
    ask(client, "INSERT INTO hc VALUES " + ",".join(f"({i},{i * 7 % 100},'c{i % 13:03d}')" for i in range(1, 301)))
    try:
        yield srv, client
    finally:
        client.close()
        srv.close()


# ------------------------------------------------------------ the table
def test_every_state_has_a_wall_counter_of_its_own():
    counters = list(tracing.HOST_STATES.values())
    assert len({id(c) for c in counters}) == len(counters) == 18
    assert all(isinstance(c, metrics.Counter) and c.name.endswith("_ns_total") for c in counters)
    # four states feed the wall counters that hand-written clock pairs fed
    assert WALL["exec.wait"] is metrics.PROGRAM_WAIT_NS and WALL["exec.readback"] is metrics.PROGRAM_READBACK_NS
    assert WALL["server.write"] is metrics.SERVER_WRITE_NS and WALL["columnar.gate"] is metrics.COLUMNAR_GATE_WAIT_NS
    # on the profiler's clock are the twelve that were: not the bottoms, nor what only waits for other threads
    assert tracing.BOTTOM_STATES == {"server.command", "distsql.task"}
    assert tracing._ANNOTATED < set(tracing.HOST_STATES) and len(tracing._ANNOTATED) == 12
    assert not tracing._ANNOTATED & (tracing.BOTTOM_STATES | {"distsql.wait_tasks", "columnar.scan"})


# ---------------------------------------------------------- conservation
def test_a_served_multi_statement_command_conserves_to_the_nanosecond(served):
    _srv, client = served
    with Charged() as m:
        ask(client, "INSERT INTO hc VALUES (900, 1, 'x'); SELECT SUM(k) FROM hc WHERE id BETWEEN 11 AND 110; "
                    "SELECT id, k, c FROM hc WHERE id <= 50")
        ask(client, "SELECT COUNT(*) FROM hc WHERE k < 37")
    assert m.commands == 2 and m.pool == 0
    assert sum(m.wall.values()) == m.handle > 0
    # the reply of every statement, the launches, and the time that no state names
    assert {"server.command", "server.write", "session.parse", "planner.plan", "exec.wait", "exec.readback",
            "session.rows"} <= set(m.wall)
    assert 0 < m.wall["server.command"] < m.handle
    # thread CPU is read once a command, at its close: the serving thread's, all states together
    assert 0 < m.cpu <= m.handle + 10_000_000 and m.pool_cpu == 0


def test_the_cpu_clock_is_read_at_a_commands_close_and_no_oftener_than_it_ticks(served, monkeypatch):
    """`thread_time_ns` is a system call of microseconds on the chip's
    host and ticks at 10 ms there: the state clock reads it as a command
    closes, `CPU_READ_NS` after the thread's last read at the soonest, and
    nowhere else; the reads' sum is still the thread's CPU."""
    _srv, client = served
    reads = {"cpu": 0, "wall": 0}
    real_cpu, real_wall = tracing.thread_time_ns, tracing.perf_counter_ns

    def cpu():
        reads["cpu"] += 1
        return real_cpu()

    def wall():
        reads["wall"] += 1
        return real_wall()

    monkeypatch.setattr(tracing, "thread_time_ns", cpu)
    monkeypatch.setattr(tracing, "perf_counter_ns", wall)
    monkeypatch.setattr(tracing, "CPU_READ_NS", 10**12)
    with Charged() as m:
        ask(client, "SELECT SUM(k) FROM hc WHERE id BETWEEN 13 AND 112; SELECT id FROM hc WHERE id = 9")
    assert reads["cpu"] == 0 and m.cpu == 0 and reads["wall"] >= 2 * len(m.wall)
    monkeypatch.setattr(tracing, "CPU_READ_NS", 0)
    with Charged() as m:
        ask(client, "SELECT SUM(k) FROM hc WHERE id BETWEEN 14 AND 113")
    assert reads["cpu"] == 1 and m.cpu > 0   # this command's CPU and the one's before it


def over_stores(store, n: int = 3) -> None:
    """The table's regions spread over `n` stores: a statement's store
    groups fan out to the dispatch executor."""
    store.cluster.set_stores(n)
    store.cluster.scatter()


def test_a_statement_whose_tasks_ran_on_the_pool_conserves_with_the_pools_share(served):
    _srv, client = served
    ask(client, "SPLIT TABLE hc BETWEEN (0) AND (300) REGIONS 4")
    over_stores(_srv.store)
    with Charged() as m:
        ask(client, "SELECT k, COUNT(*) FROM hc WHERE id > 5 GROUP BY k ORDER BY k LIMIT 3")
    assert m.pool > 0 and 0 <= m.pool_cpu <= m.pool + 10_000_000 and m.cpu > 0
    assert sum(m.wall.values()) - m.pool == m.handle
    # the serving thread waited in a state of its own while the workers' tasks ran in theirs
    assert m.wall["distsql.wait_tasks"] > 0 and m.wall["distsql.task"] > 0
    assert m.wall["distsql.task"] < m.pool   # the pool's total holds the workers' named states too


def test_a_statement_whose_one_store_batch_ran_on_its_own_thread_conserves_with_no_pool(served):
    """Four regions on one store: the batch runs on the serving thread, its
    states nest under the command's bottom, and nothing waits for a pool."""
    _srv, client = served
    ask(client, "SPLIT TABLE hc BETWEEN (0) AND (300) REGIONS 4")
    ask(client, "SELECT k, COUNT(*) FROM hc WHERE id > 4 GROUP BY k ORDER BY k LIMIT 3")   # the program is built
    inline, started = metrics.DISTSQL_INLINE_DISPATCHES.value, metrics.DISTSQL_POOL_THREADS_STARTED.value
    with Charged() as m:
        ask(client, "SELECT k, COUNT(*) FROM hc WHERE id > 5 GROUP BY k ORDER BY k LIMIT 3")
    assert metrics.DISTSQL_INLINE_DISPATCHES.value == inline + 1
    assert metrics.DISTSQL_POOL_THREADS_STARTED.value == started
    assert m.pool == 0 and m.pool_cpu == 0 and sum(m.wall.values()) == m.handle > 0
    assert not {"distsql.wait_tasks", "distsql.task"} & set(m.wall)
    assert m.wall["exec.wait"] > 0 and m.wall["server.command"] > 0
    assert not tracing.nesting_breaches


def test_pool_workers_hand_their_sums_to_the_statements_tag(sess):
    sess.execute("SPLIT TABLE hc BETWEEN (0) AND (300) REGIONS 4")
    over_stores(sess.store)
    COLLECTOR.reset()
    with Charged() as m:
        sess.execute("SELECT k, COUNT(*) FROM hc WHERE id > 7 GROUP BY k ORDER BY k LIMIT 3")
    COLLECTOR.rotate(force=True)
    (row,) = [d for w in COLLECTOR.windows_view() for d in w["digests"] if "hc" in d["sample_sql"]]
    assert m.pool > 0 and row["host_ns"]["distsql.task"] == m.wall["distsql.task"]
    assert row["host_ns"]["distsql.wait_tasks"] == m.wall["distsql.wait_tasks"]
    assert row["device_ns"] == row["host_ns"]["exec.wait"] == m.wall["exec.wait"] > 0
    # the session's thread and the workers', together
    assert row["cpu_ns"] >= m.pool_cpu >= 0 and row["cpu_ns"] > 0
    assert COLLECTOR.totals["device_ns"] == COLLECTOR.launch_device_ns


def test_a_one_store_batch_hands_the_statements_thread_sums_to_its_tag(sess):
    sess.execute("SPLIT TABLE hc BETWEEN (0) AND (300) REGIONS 4")
    sess.execute("SELECT k, COUNT(*) FROM hc WHERE id > 6 GROUP BY k ORDER BY k LIMIT 3")   # the program is built
    COLLECTOR.reset()
    with Charged() as m:
        sess.execute("SELECT k, COUNT(*) FROM hc WHERE id > 7 GROUP BY k ORDER BY k LIMIT 3")
    COLLECTOR.rotate(force=True)
    (row,) = [d for w in COLLECTOR.windows_view() for d in w["digests"] if "hc" in d["sample_sql"]]
    # everything ran on the session's thread: the tag holds every state the clock charged
    assert m.pool == 0 and row["host_ns"] == m.wall and "distsql.task" not in m.wall
    assert row["device_ns"] == m.wall["exec.wait"] > 0
    assert COLLECTOR.totals["device_ns"] == COLLECTOR.launch_device_ns


def test_sixteen_threads_charge_what_their_bottoms_measured():
    """More threads than cores, a short switch interval: the per-thread
    stacks share nothing, so the counters move by exactly what the threads'
    bottom states measured between their own two reads."""
    measured, failures = [], []

    def work():
        try:
            for _ in range(200):
                with tracing.host_state("server.command") as command:
                    with tracing.span("session.parse"):
                        pass
                    with tracing.span("distsql.root_merge"):
                        with tracing.span("exec.launch"):
                            pass
                        with tracing.span("exec.wait"):
                            pass
                measured.append(command.wall_ns)
        except Exception as e:  # noqa: BLE001 - reported below
            failures.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with Charged() as m:
            threads = [threading.Thread(target=work) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not failures and not any(t.is_alive() for t in threads) and len(measured) == 16 * 200
    assert sum(m.wall.values()) == sum(measured)
    assert set(m.wall) == {"server.command", "session.parse", "distsql.root_merge", "exec.launch", "exec.wait"}


# ------------------------------------------------- plain and traced alike
def test_a_plain_select_and_the_same_under_trace_charge_the_same_states(sess):
    sess.execute("SET tidb_enable_plan_cache = OFF")
    sess.execute("SELECT SUM(k) FROM hc WHERE id BETWEEN 1 AND 100")   # the shape's program is built, no plan cached
    sess.execute("SET tidb_enable_plan_cache = ON")
    sess.execute("BEGIN")
    with Charged() as plain:   # a miss inside a clean transaction: parsed, planned and installed
        sess.execute("SELECT SUM(k) FROM hc WHERE id BETWEEN 2 AND 101")
    with Charged() as under_trace:
        sess.execute("TRACE FORMAT='json' SELECT SUM(k) FROM hc WHERE id BETWEEN 3 AND 102")
    sess.execute("ROLLBACK")
    # no `distsql.root_merge`: the range is one region, and its lone cop task ran the whole statement (ISSUE 37)
    assert set(plain.wall) == set(under_trace.wall) == {
        "session.probe", "session.parse", "session.plan_cache", "planner.plan", "cop.decode",
        "exec.launch", "exec.wait", "exec.readback", "session.rows"}
    # the plain statement is served parse-free, here from the entry the transaction installed; TRACE always parses
    sess.execute("SELECT SUM(k) FROM hc WHERE id BETWEEN 4 AND 103")
    with Charged() as plain:
        sess.execute("SELECT SUM(k) FROM hc WHERE id BETWEEN 5 AND 104")
    with Charged() as under_trace:
        sess.execute("TRACE FORMAT='json' SELECT SUM(k) FROM hc WHERE id BETWEEN 6 AND 105")
    assert "session.parse" not in plain.wall and plain.wall["session.plan_cache"] > 0
    assert set(under_trace.wall) - {"session.parse"} == set(plain.wall)


def test_a_bare_session_flushes_at_the_close_of_its_outermost_state(sess):
    """No server, so no bottom state: every counter moves for a `Session`
    driven by hand as it did when each had a clock pair of its own."""
    with Charged() as m:
        sess.execute("SELECT SUM(k) FROM hc WHERE id BETWEEN 21 AND 120")
    assert m.handle == 0 and "server.command" not in m.wall
    assert m.wall["exec.wait"] > 0 and m.wall["exec.readback"] > 0 and m.wall["cop.decode"] > 0
    assert tracing._clock().stack == []


# -------------------------------------------------------- the nesting rule
def test_a_state_opened_inside_a_state_that_is_no_bottom_is_a_breach():
    assert not tracing.nesting_breaches   # nothing before this test breached (conftest checks after each)
    try:
        with tracing.span("cop.decode"):
            with tracing.span("exec.launch"):   # an exec.* state may sit in any state
                pass
            assert not tracing.nesting_breaches
            with tracing.span("planner.plan"):
                pass
        assert tracing.nesting_breaches == {("cop.decode", "planner.plan"): 1}
        tracing.nesting_breaches.clear()
        with tracing.host_state("server.command"):   # a bottom holds anything
            with tracing.span("planner.plan"):
                pass
            with tracing.span("distsql.wait_tasks"):
                pass
        assert not tracing.nesting_breaches
    finally:
        tracing.nesting_breaches.clear()


def test_a_statement_inside_anothers_state_runs_over_a_bottom_of_its_own(sess):
    """The root merge's row-at-a-time fallback evaluates a correlated
    subquery row by row (one that does not become a join; what only the host
    evaluates keeps the root's half at the root, ISSUE 37): each nested
    statement's states sit on a bottom of their own, so the rule holds
    inside `distsql.root_merge`."""
    sess.execute("CREATE TABLE u (id BIGINT PRIMARY KEY, tk BIGINT, w BIGINT)")
    sess.execute("INSERT INTO u VALUES (1, 7, 10), (2, 14, 20)")
    with Charged() as m:
        got = sess.execute("SELECT id, (SELECT MAX(w) FROM u WHERE u.tk >= hc.k) FROM hc WHERE id <= 2 ORDER BY id").values()
    assert got == [[1, 20], [2, 20]] and not tracing.nesting_breaches
    assert m.wall["distsql.root_merge"] > 0 and m.wall["server.command"] > 0 and m.handle == 0
    assert tracing._clock().stack == []


def test_a_plan_cache_hits_wait_is_no_descendant_of_the_plan_cache_state(sess, monkeypatch):
    sql = "SELECT SUM(k) FROM hc WHERE id BETWEEN {} AND 150"
    sess.execute(sql.format(31))
    tree = traced(sess, sql.format(32))
    (cache,) = find(tree, "session.plan_cache")
    assert cache["attrs"]["status"] == "hit" and cache["attrs"]["tier"] in ("ast", "dag")
    assert find(tree, "exec.wait") and not find(cache, "exec.wait") and not cache.get("children")
    # the parse-free serve is in no TRACE (TRACE parses): seen on the clock's stack instead
    open_states = []
    real = Session._execute_planned

    def spy(self, plan, rw=None):
        open_states.append([a.state for a in tracing._clock().stack])
        return real(self, plan, rw)

    monkeypatch.setattr(Session, "_execute_planned", spy)
    with Charged() as m:
        sess.execute(sql.format(33))
    assert open_states == [[]] and m.wall["session.plan_cache"] > 0 and "session.parse" not in m.wall


# ------------------------------------------------- the counters that stayed
def test_the_four_renamed_feeds_still_move_where_they_moved(served):
    _srv, client = served
    with Charged() as m:
        ask(client, "SELECT SUM(k) FROM hc WHERE id BETWEEN 41 AND 140")
    assert m.wall["exec.wait"] > 0 and m.wall["exec.readback"] > 0          # PROGRAM_WAIT_NS, PROGRAM_READBACK_NS
    assert 0 < m.wall["server.write"] < m.handle                              # SERVER_WRITE_NS
    ask(client, "ALTER TABLE hc SET COLUMNAR REPLICA 1")
    _srv.store.pd.tick()
    scans = metrics.COLUMNAR_SCANS.value
    with Charged() as m:
        ask(client, "SELECT k, COUNT(*) FROM hc GROUP BY k ORDER BY k LIMIT 2")
    assert metrics.COLUMNAR_SCANS.value == scans + 1
    assert m.wall["columnar.gate"] > 0 and m.wall["columnar.scan"] > 0       # COLUMNAR_GATE_WAIT_NS
    assert sum(m.wall.values()) == m.handle


def test_a_compile_is_charged_to_exec_compile_whatever_the_call_was_named(sess):
    with Charged() as first:
        sess.execute("SELECT MIN(k), MAX(k) FROM hc WHERE id BETWEEN 51 AND 160 AND k <> 3")
    with Charged() as second:
        sess.execute("SELECT MIN(k), MAX(k) FROM hc WHERE id BETWEEN 52 AND 161 AND k <> 4")
    assert first.wall["exec.compile"] > 0 and "exec.compile" not in second.wall and second.wall["exec.launch"] > 0


# ------------------------------------------------------------------ Top SQL
def test_top_sqls_device_time_of_a_columnar_statement_is_its_wait(sess):
    sess.execute("ALTER TABLE hc SET COLUMNAR REPLICA 1")
    sess.store.pd.tick()
    sql = "SELECT k, COUNT(*), SUM(id) FROM hc WHERE id > {} GROUP BY k ORDER BY k LIMIT 3"
    sess.execute(sql.format(1))
    COLLECTOR.reset()
    scans = metrics.COLUMNAR_SCANS.value
    with Charged() as m:
        sess.execute(sql.format(2))
    assert metrics.COLUMNAR_SCANS.value == scans + 1
    COLLECTOR.rotate(force=True)
    (row,) = [d for w in COLLECTOR.windows_view() for d in w["digests"]]
    assert row["device_ns"] == m.wall["exec.wait"] == row["host_ns"]["exec.wait"] > 0
    assert row["host_ns"] == m.wall
    assert {"columnar.gate", "columnar.scan"} <= set(row["host_ns"])
    assert COLLECTOR.totals["device_ns"] == COLLECTOR.launch_device_ns == row["device_ns"]
    # the digest's view is what /topsql/api/v1/digests/{digest} serves
    (window,) = COLLECTOR.digest_view(row["digest"])["windows"]
    assert window["host_ns"] == row["host_ns"]


def test_a_statement_in_a_served_command_gets_its_own_share_of_the_threads_clock(served):
    """Two statements in one command: the thread flushes once, at the
    command's end; each statement's tag takes what was charged between
    its own begin and end."""
    _srv, client = served
    ask(client, "SELECT SUM(k) FROM hc WHERE id BETWEEN 61 AND 170")   # the program is built
    COLLECTOR.reset()
    with Charged() as m:
        ask(client, "SELECT SUM(k) FROM hc WHERE id BETWEEN 62 AND 171; SELECT id FROM hc WHERE id = 7")
    COLLECTOR.rotate(force=True)
    rows = {d["sample_sql"][:10]: d for w in COLLECTOR.windows_view() for d in w["digests"]}
    agg, point = rows["SELECT SUM"], rows["SELECT id "]
    assert agg["device_ns"] == m.wall["exec.wait"] > 0 and point["device_ns"] == 0
    assert "server.write" not in agg["host_ns"]   # the reply is the command's, after the session's return
    assert sum(agg["host_ns"].values()) + sum(point["host_ns"].values()) < m.handle


# ----------------------------------------------------------------- failures
def test_an_exception_inside_a_state_leaves_the_stack_balanced(sess):
    with pytest.raises(RuntimeError, match="inside"):
        with tracing.host_state("server.command"):
            with tracing.span("distsql.root_merge"):
                with tracing.span("exec.wait"):
                    raise RuntimeError("inside")
    assert tracing._clock().stack == []
    # a statement that fails below the launch boundary
    from tidb_tpu.util import failpoint

    with failpoint.enabled("cop-debug-raise"), pytest.raises(Exception):
        with tracing.host_state("server.command"):
            sess.execute("SELECT SUM(k) FROM hc WHERE id BETWEEN 71 AND 180 AND c REGEXP '['")
    assert tracing._clock().stack == []
    with Charged() as m:
        sess.execute("SELECT SUM(k) FROM hc WHERE id BETWEEN 72 AND 181")
    assert m.wall["exec.wait"] > 0 and tracing._clock().stack == []


# --------------------------------------------------------------------- tools
def test_the_clock_probe_times_the_clocks_and_a_state_pair(capsys, monkeypatch):
    """`tools/host_clock_probe.py` is how the chip host's 6 us CPU clock
    was found: it has to keep running against this tree."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location("host_clock_probe", os.path.join(root, "tools", "host_clock_probe.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    monkeypatch.setattr(probe, "PAIRS", 1600)
    probe.pairs(root)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [(r["span"], r["threads"]) for r in lines] == [
        ("exec.wait", 1), ("exec.wait", 16), ("cop.execute", 1), ("cop.execute", 16)]
    assert all(r["ns_per_pair"] > 0 for r in lines) and tracing._clock().stack == []
