"""Transactions: Percolator 2PC engine (store/txn.py) + session txn layer
(ref: unistore/tikv/mvcc.go prewrite/commit, lockstore; client-go 2PC;
pkg/session LazyTxn; pkg/executor/union_scan.go read-your-writes)."""

import pytest

from tidb_tpu.sql.catalog import Catalog
from tidb_tpu.sql.session import Session, SQLError
from tidb_tpu.store import TPUStore
from tidb_tpu.store.txn import KeyIsLocked, TxnEngine, WriteConflict


@pytest.fixture()
def pair():
    store, cat = TPUStore(), Catalog()
    s1, s2 = Session(store, cat), Session(store, cat)
    s1.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT)")
    s1.execute("INSERT INTO t VALUES (1,10),(2,20)")
    return s1, s2


# ---------------------------------------------------------------- engine


def test_engine_prewrite_commit():
    from tidb_tpu.store.kv import MemKV

    kv = MemKV()
    eng = TxnEngine(kv)
    eng.commit_txn({b"a": b"1", b"b": b"2"}, start_ts=10, commit_ts=11)
    assert kv.get(b"a", 11) == b"1" and kv.get(b"b", 11) == b"2"
    assert kv.get(b"a", 10) is None  # snapshot before commit_ts


def test_engine_write_conflict():
    from tidb_tpu.store.kv import MemKV

    kv = MemKV()
    eng = TxnEngine(kv)
    eng.commit_txn({b"a": b"1"}, 10, 15)
    with pytest.raises(WriteConflict):
        eng.commit_txn({b"a": b"2"}, 12, 16)  # started before the commit landed
    assert kv.get(b"a", 100) == b"1"
    assert not eng.locks  # failed prewrite leaves no locks behind


def test_engine_key_is_locked():
    from tidb_tpu.store.kv import MemKV

    eng = TxnEngine(MemKV())
    eng.prewrite({b"a": b"1"}, b"a", 10)
    with pytest.raises(KeyIsLocked):
        eng.prewrite({b"a": b"2"}, b"a", 12)
    eng.rollback([b"a"], 10)
    eng.commit_txn({b"a": b"2"}, 12, 13)


def test_engine_pessimistic_converts():
    from tidb_tpu.store.kv import MemKV

    kv = MemKV()
    eng = TxnEngine(kv)
    eng.acquire_pessimistic([b"a"], b"a", 10, 10)
    with pytest.raises(KeyIsLocked):
        eng.acquire_pessimistic([b"a"], b"a", 20, 20)
    eng.commit_txn({b"a": b"x"}, 10, 12)
    assert kv.get(b"a", 12) == b"x"
    assert not eng.locks


# ---------------------------------------------------------------- session


def test_read_your_writes_and_isolation(pair):
    s1, s2 = pair
    s1.execute("BEGIN")
    s1.execute("UPDATE t SET v = 99 WHERE id = 1")
    s1.execute("INSERT INTO t VALUES (3,30)")
    s1.execute("DELETE FROM t WHERE id = 2")
    assert s1.execute("SELECT * FROM t ORDER BY id").values() == [[1, 99], [3, 30]]
    # other session sees the pre-txn snapshot
    assert s2.execute("SELECT * FROM t ORDER BY id").values() == [[1, 10], [2, 20]]
    s1.execute("COMMIT")
    assert s2.execute("SELECT * FROM t ORDER BY id").values() == [[1, 99], [3, 30]]


def test_rollback_discards(pair):
    s1, s2 = pair
    s1.execute("BEGIN")
    s1.execute("UPDATE t SET v = 0")
    s1.execute("ROLLBACK")
    assert s1.execute("SELECT * FROM t ORDER BY id").values() == [[1, 10], [2, 20]]


def test_repeatable_read_snapshot(pair):
    s1, s2 = pair
    s1.execute("BEGIN")
    assert s1.execute("SELECT v FROM t WHERE id = 1").values() == [[10]]
    s2.execute("UPDATE t SET v = 77 WHERE id = 1")
    # repeatable read: s1 still sees its snapshot
    assert s1.execute("SELECT v FROM t WHERE id = 1").values() == [[10]]
    s1.execute("COMMIT")
    assert s1.execute("SELECT v FROM t WHERE id = 1").values() == [[77]]


def test_pessimistic_lock_conflict(pair):
    s1, s2 = pair
    s1.execute("BEGIN")
    s1.execute("UPDATE t SET v = 1 WHERE id = 2")
    # the lock is waited for, here by the holder's own thread: the wait runs out
    s2.execute("SET innodb_lock_wait_timeout = 1")
    with pytest.raises(SQLError, match="Lock wait timeout exceeded") as ei:
        s2.execute("UPDATE t SET v = 2 WHERE id = 2")
    assert ei.value.code == 1205
    s1.execute("COMMIT")
    s2.execute("UPDATE t SET v = 2 WHERE id = 2")
    assert s2.execute("SELECT v FROM t WHERE id = 2").values() == [[2]]


def test_optimistic_write_conflict(pair):
    s1, s2 = pair
    s1.execute("SET tidb_txn_mode = 'optimistic'")
    s1.execute("BEGIN")
    s1.execute("UPDATE t SET v = 5 WHERE id = 1")
    s2.execute("UPDATE t SET v = 7 WHERE id = 1")
    with pytest.raises(SQLError, match="conflict"):
        s1.execute("COMMIT")
    assert s2.execute("SELECT v FROM t WHERE id = 1").values() == [[7]]


def test_select_for_update_locks(pair):
    s1, s2 = pair
    s1.execute("BEGIN")
    s1.execute("SELECT * FROM t WHERE id = 2 FOR UPDATE")
    s2.execute("SET innodb_lock_wait_timeout = 1")
    with pytest.raises(SQLError) as ei:
        s2.execute("DELETE FROM t WHERE id = 2")
    assert ei.value.code == 1205
    s1.execute("ROLLBACK")
    s2.execute("DELETE FROM t WHERE id = 2")
    assert s2.execute("SELECT count(*) FROM t").values() == [[1]]


def test_txn_aggregate_sees_own_writes(pair):
    s1, _ = pair
    s1.execute("BEGIN")
    s1.execute("INSERT INTO t VALUES (10, 100), (11, 200)")
    got = s1.execute("SELECT count(*), sum(v) FROM t").values()
    assert [[got[0][0], int(str(got[0][1]))]] == [[4, 330]]
    s1.execute("COMMIT")
    assert s1.execute("SELECT count(*) FROM t").values() == [[4]]


def test_txn_join_with_dirty_table(pair):
    s1, _ = pair
    s1.execute("CREATE TABLE u (id INT PRIMARY KEY, tv INT)")
    s1.execute("INSERT INTO u VALUES (1, 10)")
    s1.execute("BEGIN")
    s1.execute("INSERT INTO u VALUES (2, 20)")
    got = s1.execute("SELECT t.id, u.id FROM t JOIN u ON t.v = u.tv ORDER BY t.id").values()
    assert got == [[1, 1], [2, 2]]
    s1.execute("ROLLBACK")
    got = s1.execute("SELECT t.id, u.id FROM t JOIN u ON t.v = u.tv ORDER BY t.id").values()
    assert got == [[1, 1]]


def test_ddl_implicitly_commits(pair):
    s1, s2 = pair
    s1.execute("BEGIN")
    s1.execute("UPDATE t SET v = 1 WHERE id = 1")
    s1.execute("CREATE TABLE z (a INT PRIMARY KEY)")  # implicit commit
    assert s2.execute("SELECT v FROM t WHERE id = 1").values() == [[1]]
    assert s1.txn is None


def test_begin_commits_previous(pair):
    s1, s2 = pair
    s1.execute("BEGIN")
    s1.execute("UPDATE t SET v = 42 WHERE id = 1")
    s1.execute("BEGIN")  # implicitly commits the first txn
    assert s2.execute("SELECT v FROM t WHERE id = 1").values() == [[42]]
    s1.execute("ROLLBACK")


def test_unique_check_sees_buffer(pair):
    s1, _ = pair
    s1.execute("CREATE UNIQUE INDEX uv ON t (v)")
    s1.execute("BEGIN")
    s1.execute("INSERT INTO t VALUES (5, 50)")
    with pytest.raises(SQLError, match="duplicate"):
        s1.execute("INSERT INTO t VALUES (6, 50)")  # dup within the buffer
    s1.execute("ROLLBACK")


def test_failed_statement_in_autocommit_leaves_no_trace(pair):
    s1, _ = pair
    with pytest.raises(SQLError):
        s1.execute("INSERT INTO t VALUES (1, 999)")  # dup pk
    assert s1.execute("SELECT count(*) FROM t").values() == [[2]]
    assert not s1.store.txn.locks


class TestReplaceIgnoreUnique:
    """ADVICE r2: REPLACE INTO / INSERT IGNORE on a SECONDARY unique-index
    conflict must follow MySQL semantics (ref: executor/replace.go
    removeRow; insert IGNORE duplicate-as-warning), not raise."""

    def _mk(self):
        from tidb_tpu.sql import Session

        s = Session()
        s.execute("create table t (id bigint primary key, u bigint, v varchar(10), unique key uk (u))")
        s.execute("insert into t values (1, 10, 'a'), (2, 20, 'b')")
        return s

    def test_replace_deletes_conflicting_row(self):
        s = self._mk()
        r = s.execute("replace into t values (3, 10, 'c')")  # conflicts with id=1 on uk
        assert r.affected == 2  # one delete + one insert
        rows = sorted((int(x[0].val), int(x[1].val), str(x[2].val)) for x in s.execute("select * from t").rows)
        assert rows == [(2, 20, "b"), (3, 10, "c")]

    def test_replace_conflicting_pk_and_unique(self):
        s = self._mk()
        # conflicts with id=2 on PK AND id=1 on uk: both rows die
        r = s.execute("replace into t values (2, 10, 'z')")
        assert r.affected == 3  # MySQL: uk-row delete + in-place delete+insert
        rows = sorted((int(x[0].val), int(x[1].val)) for x in s.execute("select * from t").rows)
        assert rows == [(2, 10)]

    def test_insert_ignore_skips_unique_conflict(self):
        s = self._mk()
        r = s.execute("insert ignore into t values (3, 10, 'c'), (4, 40, 'd')")
        assert r.affected == 1  # only (4,40,'d') lands
        rows = sorted(int(x[0].val) for x in s.execute("select * from t").rows)
        assert rows == [1, 2, 4]


class TestNamedSavepoints:
    def test_rollback_to_savepoint(self):
        from tidb_tpu.sql import Session

        s = Session()
        s.execute("create table sv (a bigint primary key)")
        s.execute("begin")
        s.execute("insert into sv values (1)")
        s.execute("savepoint sp1")
        s.execute("insert into sv values (2)")
        s.execute("rollback to savepoint sp1")
        s.execute("commit")
        rows = sorted(int(r[0].val) for r in s.execute("select * from sv").rows)
        assert rows == [1]

    def test_rollback_to_missing_savepoint_errors(self):
        from tidb_tpu.sql import Session

        s = Session()
        s.execute("create table sv2 (a bigint)")
        s.execute("begin")
        try:
            s.execute("rollback to savepoint nope")
            raise AssertionError("expected error")
        except Exception as exc:
            assert "does not exist" in str(exc)
        s.execute("rollback")
