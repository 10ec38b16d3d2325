"""Spans and counters of the write path, over the wire with `TRACE
FORMAT='json'`: a pessimistic DML statement carries `txn.lock` (attrs
`keys`, `waited`, `wait_ms`, `outcome`) under its `session.execute`, a
COMMIT carries `txn.prewrite` and `txn.commit` (`keys`), and inside the
commit `store.cache_drop` (`entries`, `device_bytes`); a statement that
waited for a lock whose holder then committed carries `waited` and a
`txn.retry` (`for_update_ts`).  They are plain spans, no host states: the
state clock's table is as it was and its nesting rule holds (the suite's
`host_states_nest_by_the_rule`)."""

import json
import threading
import time

import pytest

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.server.client import ClientError
from tidb_tpu.util import metrics, tracing

HOST_STATES = {
    "server.command", "session.probe", "session.parse", "session.plan_cache", "planner.plan", "session.rows",
    "distsql.wait_tasks", "distsql.task", "cop.decode", "mesh.stack", "columnar.gate", "columnar.scan",
    "exec.compile", "exec.launch", "exec.wait", "exec.readback", "distsql.root_merge", "server.write",
}
WRITE_SPANS = {"txn.lock", "txn.prewrite", "txn.commit", "store.cache_drop", "txn.retry"}


@pytest.fixture
def wire():
    srv = MySQLServer(port=0)
    srv.start_background()
    conns = [MiniClient(srv.host, srv.port, timeout=60.0) for _ in range(3)]
    conns[0].query("create table w (id int not null, k int not null, primary key (id), key k_1 (k))")
    conns[0].query("insert into w values (1, 10), (2, 20), (3, 30)")
    conns[0].query("select sum(k) from w where id between 1 and 3")   # a region batch for the commit to drop
    yield conns
    for c in conns:
        c.close()
    srv.close()


def traced(conn, sql: str) -> dict:
    return json.loads(conn.query("trace format='json' " + sql)[1][0][0])


def find(node: dict, name: str) -> list:
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]


def under_execute(tree: dict, name: str) -> list:
    (execute,) = find(tree, "session.execute")
    return find(execute, name)


def test_a_traced_write_transaction_carries_the_write_spans(wire):
    a = wire[1]
    before = metrics.TXN_LOCK_WAITS.value
    a.query("begin")
    (lock,) = under_execute(traced(a, "update w set k = k + 1 where id = 1"), "txn.lock")
    assert lock["attrs"] == {"keys": 1, "waited": False, "wait_ms": 0.0, "outcome": "locked"}
    assert under_execute(traced(a, "delete from w where id = 2"), "txn.lock")
    (insert_lock,) = under_execute(traced(a, "insert into w values (2, 21)"), "txn.lock")
    assert insert_lock["attrs"]["outcome"] == "locked"
    commit = traced(a, "commit")
    (prewrite,) = under_execute(commit, "txn.prewrite")
    (done,) = under_execute(commit, "txn.commit")
    assert prewrite["attrs"] == {"keys": 6} and done["attrs"] == {"keys": 6}   # 2 rows; 2 index entries gone, 2 put
    (drop,) = find(done, "store.cache_drop")
    assert drop["attrs"]["entries"] >= 1 and drop["attrs"]["device_bytes"] >= 0
    assert metrics.TXN_LOCK_WAITS.value == before   # nothing waited
    assert not WRITE_SPANS & set(tracing.HOST_STATES) and set(tracing.HOST_STATES) == HOST_STATES


def test_a_waited_lock_carries_waited_and_the_retry(wire):
    admin, a, b = wire
    waits, ns, retries = (metrics.TXN_LOCK_WAITS.value, metrics.TXN_LOCK_WAIT_NS.value,
                          metrics.TXN_PESSIMISTIC_RETRIES.value)
    a.query("begin")
    a.query("update w set k = k + 1 where id = 3")
    b.query("begin")
    got = {}
    t = threading.Thread(target=lambda: got.update(tree=traced(b, "update w set k = k + 1 where id = 3")))
    t.start()
    deadline = time.perf_counter() + 10
    while metrics.TXN_LOCK_WAITS.value == waits:
        assert time.perf_counter() < deadline
        time.sleep(0.01)
    time.sleep(0.05)
    a.query("commit")
    t.join(30)
    locks = under_execute(got["tree"], "txn.lock")
    assert locks[0]["attrs"]["waited"] is True and locks[0]["attrs"]["wait_ms"] >= 50
    assert locks[0]["attrs"]["outcome"] == "locked"
    (retry,) = under_execute(got["tree"], "txn.retry")   # a's commit came after b's for_update_ts
    assert retry["attrs"]["for_update_ts"] > 0 and [c["name"] for c in find(retry, "txn.lock")] == ["txn.lock"]
    b.query("commit")
    assert admin.query("select k from w where id = 3")[1] == [["32"]]
    assert metrics.TXN_LOCK_WAITS.value - waits == 1 and metrics.TXN_LOCK_WAIT_NS.value - ns >= 50_000_000
    assert metrics.TXN_PESSIMISTIC_RETRIES.value - retries == 1


def test_a_lock_wait_that_times_out_says_so(wire):
    _, a, b = wire
    timeouts = metrics.TXN_LOCK_WAIT_TIMEOUTS.value
    a.query("begin")
    a.query("update w set k = 0 where id = 1")
    b.query("set innodb_lock_wait_timeout = 1")
    b.query("begin")
    tree = traced(b, "update w set k = 1 where id = 1")   # TRACE answers the failure as a row
    assert "Lock wait timeout exceeded" in tree["attrs"]["error"]
    (lock,) = under_execute(tree, "txn.lock")
    assert lock["attrs"]["outcome"] == "timeout" and lock["attrs"]["waited"] is True
    assert metrics.TXN_LOCK_WAIT_TIMEOUTS.value - timeouts == 1
    with pytest.raises(ClientError) as e:   # untraced, the errno
        b.query("update w set k = 1 where id = 1")
    assert e.value.code == 1205
    a.query("rollback")
    b.query("rollback")
