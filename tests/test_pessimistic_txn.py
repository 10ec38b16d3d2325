"""Pessimistic transactions as TiDB serves them, over the wire with two
connections: a lock held by another transaction is waited for (woken by its
commit, its rollback or its connection's close), up to
innodb_lock_wait_timeout (errno 1205); a wait that would close a cycle is
refused with 1213 and that transaction rolled back, so the other proceeds;
UPDATE and DELETE compute on the row as committed at a for_update_ts drawn
under the lock, an INSERT checks for a duplicate there, SELECTs keep the
snapshot; optimistic mode answers a write conflict with 9007."""

import threading
import time

import pytest

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.server.client import ClientError
from tidb_tpu.util import metrics

K0 = {i: 10 * i for i in range(1, 9)}


@pytest.fixture
def wire():
    srv = MySQLServer(port=0)
    srv.start_background()
    conns = []

    def connect():
        c = MiniClient(srv.host, srv.port, timeout=60.0)
        conns.append(c)
        return c

    admin = connect()
    admin.query("create table t (id int not null, k int not null, c varchar(20) not null default '', "
                "primary key (id), key k_1 (k))")
    admin.query("insert into t (id, k) values " + ",".join(f"({i},{k})" for i, k in K0.items()))
    yield connect, admin
    for c in conns:
        try:
            c.close()
        except OSError:
            pass
    srv.close()


class Background:
    """One statement sent on another thread: its answer or its error."""

    def __init__(self, conn, sql: str):
        self.answer = self.error = None
        self._t = threading.Thread(target=self._run, args=(conn, sql), daemon=True)
        self._t.start()

    def _run(self, conn, sql):
        try:
            self.answer = conn.query(sql)
        except ClientError as e:
            self.error = e

    def waiting(self) -> bool:
        return self._t.is_alive()

    def join(self, timeout: float = 30.0):
        self._t.join(timeout)
        assert not self._t.is_alive(), "the statement never answered"
        return self


def k_of(conn, i: int) -> int:
    return int(conn.query(f"select k from t where id = {i}")[1][0][0])


def wait_moved(counter, before: int, timeout: float = 10.0) -> None:
    """Until `counter` has moved past `before`: a statement is waiting."""
    deadline = time.perf_counter() + timeout
    while counter.value <= before:
        assert time.perf_counter() < deadline, "nothing waited"
        time.sleep(0.01)


def test_waiter_applies_k_plus_one_to_the_committed_value(wire):
    connect, admin = wire
    a, b = connect(), connect()
    waits, retries = metrics.TXN_LOCK_WAITS.value, metrics.TXN_PESSIMISTIC_RETRIES.value
    a.query("begin")
    assert a.query("update t set k = k + 1 where id = 1") == 1
    b.query("begin")
    pending = Background(b, "update t set k = k + 1 where id = 1")
    wait_moved(metrics.TXN_LOCK_WAITS, waits)
    assert pending.waiting()
    a.query("commit")
    assert pending.join().error is None and pending.answer == 1   # waited, then read a's row at for_update_ts
    b.query("commit")
    assert k_of(admin, 1) == K0[1] + 2
    assert metrics.TXN_PESSIMISTIC_RETRIES.value - retries == 1   # the row changed while b waited


def test_rollback_wakes_the_waiter(wire):
    connect, admin = wire
    a, b = connect(), connect()
    waits = metrics.TXN_LOCK_WAITS.value
    a.query("begin")
    a.query("update t set k = 0 where id = 2")
    b.query("begin")
    pending = Background(b, "update t set k = k + 1 where id = 2")
    wait_moved(metrics.TXN_LOCK_WAITS, waits)
    a.query("rollback")
    assert pending.join().answer == 1
    b.query("commit")
    assert k_of(admin, 2) == K0[2] + 1


def test_lock_wait_timeout_answers_1205(wire):
    connect, admin = wire
    a, b = connect(), connect()
    timeouts = metrics.TXN_LOCK_WAIT_TIMEOUTS.value
    a.query("begin")
    a.query("update t set k = k + 1 where id = 3")
    b.query("set innodb_lock_wait_timeout = 1")
    b.query("begin")
    t0 = time.perf_counter()
    with pytest.raises(ClientError) as e:
        b.query("update t set k = k + 1 where id = 3")
    assert e.value.code == 1205 and "Lock wait timeout exceeded" in e.value.message
    assert time.perf_counter() - t0 >= 1.0
    assert metrics.TXN_LOCK_WAIT_TIMEOUTS.value - timeouts == 1
    assert b.query("select k from t where id = 4")[1] == [[str(K0[4])]]   # the statement failed, not the txn
    b.query("rollback")
    a.query("commit")
    assert k_of(admin, 3) == K0[3] + 1


def test_crossing_pair_answers_1213_to_one_side(wire):
    connect, admin = wire
    a, b = connect(), connect()
    waits, deadlocks = metrics.TXN_LOCK_WAITS.value, metrics.TXN_DEADLOCKS.value
    a.query("begin")
    b.query("begin")
    a.query("update t set k = k + 1 where id = 5")
    b.query("update t set k = k + 100 where id = 6")
    pending = Background(a, "update t set k = k + 1 where id = 6")   # a waits for b
    wait_moved(metrics.TXN_LOCK_WAITS, waits)
    with pytest.raises(ClientError) as e:
        b.query("update t set k = k + 100 where id = 5")   # b would wait for a: a cycle
    assert e.value.code == 1213 and "Deadlock found" in e.value.message
    assert pending.join().error is None and pending.answer == 1   # b's rollback let a through
    a.query("commit")
    assert metrics.TXN_DEADLOCKS.value - deadlocks == 1
    assert (k_of(admin, 5), k_of(admin, 6)) == (K0[5] + 1, K0[6] + 1)   # nothing of b's
    b.query("rollback")   # b's transaction is gone already: a no-op


def test_closed_connection_releases_its_locks(wire):
    connect, admin = wire
    a, b = connect(), connect()
    waits = metrics.TXN_LOCK_WAITS.value
    a.query("begin")
    a.query("update t set k = 0 where id = 7")
    b.query("begin")
    pending = Background(b, "update t set k = k + 1 where id = 7")
    wait_moved(metrics.TXN_LOCK_WAITS, waits)
    a.close()
    assert pending.join().answer == 1
    b.query("commit")
    assert k_of(admin, 7) == K0[7] + 1


def test_two_delete_insert_on_one_id_both_commit(wire):
    connect, admin = wire
    a, b = connect(), connect()
    waits = metrics.TXN_LOCK_WAITS.value
    a.query("begin")
    assert a.query("delete from t where id = 8") == 1
    assert a.query("insert into t (id, k, c) values (8, 100, 'a')") == 1
    b.query("begin")
    pending = Background(b, "delete from t where id = 8")
    wait_moved(metrics.TXN_LOCK_WAITS, waits)
    a.query("commit")
    assert pending.join().answer == 1   # a's row, read at for_update_ts
    assert b.query("insert into t (id, k, c) values (8, 200, 'b')") == 1   # no 1062
    b.query("commit")
    assert admin.query("select id, k, c from t where id = 8")[1] == [["8", "200", "b"]]
    assert admin.query("select id from t where k = 100")[1] == []   # a's index entry went with its row


def test_insert_waits_for_a_deleting_holder():
    """An INSERT of an id another transaction holds waits for it, then
    checks for a duplicate against what it committed."""
    srv = MySQLServer(port=0)
    srv.start_background()
    a, b, admin = (MiniClient(srv.host, srv.port, timeout=60.0) for _ in range(3))
    try:
        admin.query("create table u (id int not null, v int, primary key (id))")
        admin.query("insert into u values (1, 1)")
        waits = metrics.TXN_LOCK_WAITS.value
        a.query("begin")
        a.query("delete from u where id = 1")
        b.query("begin")
        pending = Background(b, "insert into u values (1, 2)")
        wait_moved(metrics.TXN_LOCK_WAITS, waits)
        a.query("commit")
        assert pending.join().answer == 1   # the row a deleted is gone at b's for_update_ts
        b.query("commit")
        assert admin.query("select v from u where id = 1")[1] == [["2"]]
    finally:
        for c in (a, b, admin):
            c.close()
        srv.close()


def test_optimistic_mode_answers_9007(wire):
    connect, admin = wire
    a = connect()
    conflicts = metrics.TXN_WRITE_CONFLICTS.value
    a.query("set tidb_txn_mode = 'optimistic'")
    a.query("begin")
    a.query("update t set k = 0 where id = 4")
    admin.query("update t set k = k + 1 where id = 4")
    with pytest.raises(ClientError) as e:
        a.query("commit")
    assert e.value.code == 9007 and "Write conflict" in e.value.message
    assert metrics.TXN_WRITE_CONFLICTS.value - conflicts == 1
    assert k_of(admin, 4) == K0[4] + 1


def test_select_keeps_the_snapshot(wire):
    connect, admin = wire
    a = connect()
    a.query("begin")
    assert k_of(a, 1) == K0[1]   # the snapshot is taken
    admin.query("update t set k = k + 10 where id in (1, 2)")
    assert (k_of(a, 1), k_of(a, 2)) == (K0[1], K0[2])   # SELECTs read the snapshot
    assert a.query("update t set k = k + 1 where id = 1") == 1   # DML reads the row as committed
    assert (k_of(a, 1), k_of(a, 2)) == (K0[1] + 11, K0[2])   # its own write, and the snapshot beside it
    a.query("commit")
    assert (k_of(admin, 1), k_of(admin, 2)) == (K0[1] + 11, K0[2] + 10)


def test_stress_no_increment_is_lost():
    """More threads than cores, a short switch interval: each transaction
    adds 1 to three of four rows in its own order (deadlocks among them),
    restarts on 1213, and every committed increment is in the final sum."""
    import os
    import random
    import sys

    from tidb_tpu.sql import Session, SQLError
    from tidb_tpu.sql.catalog import Catalog
    from tidb_tpu.store import TPUStore

    store, cat = TPUStore(), Catalog()
    Session(store, cat).execute("create table s (id int primary key, k int not null)")
    Session(store, cat).execute("insert into s values (1, 0), (2, 0), (3, 0), (4, 0)")
    threads_n = max(2 * (os.cpu_count() or 4), 16)
    committed = [0] * threads_n
    restarts = [0] * threads_n

    def worker(i: int) -> None:
        s, rng = Session(store, cat), random.Random(i)
        for _ in range(6):
            while True:
                try:
                    s.execute("begin")
                    for row in rng.sample([1, 2, 3, 4], 3):
                        s.execute(f"update s set k = k + 1 where id = {row}")
                    s.execute("commit")
                    committed[i] += 3
                    break
                except SQLError as e:
                    assert e.code == 1213, e
                    restarts[i] += 1
                    s.execute("rollback")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,), daemon=True) for i in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    total = Session(store, cat).execute("select sum(k) from s").values()[0][0]
    assert int(str(total)) == sum(committed) == threads_n * 6 * 3
    assert not store.txn.locks
