"""The row store keeps what it decoded (PR 33): a region's decoded chunk and
its device batch are keyed by the region's data version, not by the
statement's timestamp, under the snapshot rule of the cop result cache
(`store._SnapshotCache`, `TPUStore._may_file`), and both are LRUs bounded by
bytes.  A later statement with no write between finds what an earlier one
decoded; a snapshot that predates a commit misses, reads its own rows and
files nothing; any write, an epoch change and `evict_caches()` drop what is
there.  Small tables on the CPU, the store driven directly and through two
sessions."""

import sys
import threading

import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql import full_table_ranges, handle_ranges
from tidb_tpu.exec import ColumnInfo, DAGRequest, TableScan
from tidb_tpu.sql import Session
from tidb_tpu.store import CopRequest, TPUStore
from tidb_tpu.store.store import _SnapshotCache
from tidb_tpu.types import Datum, new_longlong
from tidb_tpu.util import metrics

TID = 77
FT = new_longlong()
DAG = DAGRequest((TableScan(TID, (ColumnInfo(1, FT),)),), output_offsets=(0,))
FULL = full_table_ranges(TID)
NAMES = ("COP_DECODE_HITS", "COP_DECODE_MISSES", "COP_DECODE_EVICTIONS", "COP_DECODE_DEVICE_BYTES", "NATIVE_DECODES",
         "COP_CACHE_HITS")


def fill(n=64, ts=10) -> TPUStore:
    store = TPUStore()
    for h in range(n):
        store.put_row(TID, h, [1], [Datum.i64(h * 3)], ts=ts)
    return store


def region(store):
    (r,) = store.cluster.regions()
    return r


def vals(chunk):
    return [r[0].val for r in chunk.rows()]


def read(store, ts, ranges=FULL, device=True):
    return store._region_read(region(store), ranges, DAG, ts, device=device)


class Moved:
    """The always-on counters, read around a block."""

    def __enter__(self):
        self.before = {n: getattr(metrics, n).value for n in NAMES}
        return self

    def __exit__(self, *exc):
        self.by = {n: getattr(metrics, n).value - self.before[n] for n in NAMES}


def test_a_later_statement_with_no_write_between_hits():
    store = fill()
    with Moved() as first:
        ch1, b1, hit1 = read(store, 100)
    with Moved() as second:
        ch2, b2, hit2 = read(store, 200)
    assert (hit1, hit2) == (False, True) and ch2 is ch1 and b2 is b1
    assert vals(ch1) == [h * 3 for h in range(64)]
    assert (first.by["COP_DECODE_MISSES"], first.by["COP_DECODE_HITS"], first.by["NATIVE_DECODES"]) == (1, 0, 1)
    assert (second.by["COP_DECODE_MISSES"], second.by["COP_DECODE_HITS"], second.by["NATIVE_DECODES"]) == (0, 1, 0)
    assert first.by["COP_DECODE_DEVICE_BYTES"] == b1.nbytes() and second.by["COP_DECODE_DEVICE_BYTES"] == 0


def test_region_chunk_alone_hits_and_the_batch_is_then_uploaded_once():
    """`batch_coprocessor` and the mesh tier take the host chunk and stack
    it themselves: a chunk-only read hits without a batch; a read that
    wants the batch too misses once (the upload), then hits."""
    store = fill()
    ch = store.region_chunk(region(store), FULL, DAG, 100)
    with Moved() as m:
        assert store.region_chunk(region(store), FULL, DAG, 101) is ch
        _, b1, hit1 = read(store, 102)
        _, b2, hit2 = read(store, 103)
    assert (hit1, hit2) == (False, True) and b2 is b1
    assert (m.by["COP_DECODE_HITS"], m.by["COP_DECODE_MISSES"], m.by["NATIVE_DECODES"]) == (2, 1, 0)


def test_a_column_whose_type_was_modified_is_another_key():
    """MODIFY COLUMN swaps the column's field type and writes nothing to the
    kv, so the write version stays: the key names each column by the result
    cache's fingerprint (id, type, flag, flen, decimal, default), and the
    next read decodes the stored bytes as the new type says."""
    from tidb_tpu.types import new_decimal

    store = TPUStore()
    for h in range(8):
        store.put_row(TID, h, [1], [Datum.dec(f"{h}.25")], ts=10)

    def dag_of(ft):
        return DAGRequest((TableScan(TID, (ColumnInfo(1, ft),)),), output_offsets=(0,))

    r = region(store)
    ch1, b1, _ = store._region_read(r, FULL, dag_of(new_decimal(10, 2)), 100, device=True)
    with Moved() as m:
        ch2, b2, hit2 = store._region_read(r, FULL, dag_of(new_decimal(12, 4)), 101, device=True)
        ch3, b3, hit3 = store._region_read(r, FULL, dag_of(new_decimal(10, 2)), 102, device=True)
    assert (hit2, hit3) == (False, True) and ch2 is not ch1 and b2 is not b1 and ch3 is ch1 and b3 is b1
    assert (m.by["COP_DECODE_MISSES"], m.by["NATIVE_DECODES"]) == (1, 1)
    assert [c.ft.decimal for c in ch1.columns] == [2] and [c.ft.decimal for c in ch2.columns] == [4]
    assert len(store._chunk_cache) == len(store._batch_cache) == 2


def test_modify_column_with_no_write_between_reads_at_the_new_scale():
    s = Session()
    s.execute("create table t (id bigint primary key, d decimal(10,2))")
    s.execute("insert into t values (1, 1.25), (2, 2.50), (3, 3.75)")
    q = "select sum(d), max(d) from t where d > 1.5"
    before = [[str(d.val) for d in r] for r in s.execute(q).rows]
    assert before == [["6.25", "3.75"]]
    with s.store._cop_lock:
        ver = s.store._write_ver
    s.execute("alter table t modify column d decimal(12,4)")
    with s.store._cop_lock:
        assert s.store._write_ver == ver      # the DDL wrote nothing: only the key tells the types apart
    with Moved() as m:
        after = [[str(d.val) for d in r] for r in s.execute(q).rows]
    assert after == [["6.2500", "3.7500"]]
    assert m.by["COP_DECODE_MISSES"] == 1 and m.by["COP_DECODE_HITS"] == 0 and m.by["COP_CACHE_HITS"] == 0


def test_any_committed_write_makes_the_next_read_miss_and_see_the_new_row():
    store = fill()
    ch1, b1, _ = read(store, 100)
    store.put_row(TID + 1, 0, [1], [Datum.i64(7)], ts=110)   # another table: the version is the store's
    with Moved() as other:
        ch2, _, hit2 = read(store, 120)
    store.put_row(TID, 64, [1], [Datum.i64(192)], ts=130)
    with Moved() as own:
        ch3, b3, hit3 = read(store, 140)
    assert (hit2, hit3) == (False, False) and ch2 is not ch1 and b3 is not b1
    assert vals(ch2) == vals(ch1) and vals(ch3) == vals(ch1) + [192]
    assert other.by["NATIVE_DECODES"] == own.by["NATIVE_DECODES"] == 1
    # the write dropped the dead entries: one chunk and one batch are held, the gauge says so
    assert len(store._chunk_cache) == len(store._batch_cache) == 1
    assert store._batch_cache.used == b3.nbytes()


def test_a_snapshot_that_predates_a_commit_misses_reads_its_own_rows_and_files_nothing():
    store = fill()
    store.put_row(TID, 64, [1], [Datum.i64(192)], ts=150)
    old = [h * 3 for h in range(64)]
    with Moved() as pinned:
        ch_old, _, hit_old = read(store, 100)
    assert not hit_old and vals(ch_old) == old
    assert len(store._chunk_cache) == len(store._batch_cache) == 0 and pinned.by["COP_DECODE_DEVICE_BYTES"] == 0
    ch_new, _, _ = read(store, 200)              # a fresh statement beside it: sees the row, and files
    assert vals(ch_new) == old + [192] and len(store._chunk_cache) == 1
    with Moved() as again:
        ch_old2, _, hit_old2 = read(store, 100)  # the entry's start_ts is 200: the old snapshot still misses
    assert not hit_old2 and vals(ch_old2) == old and again.by["NATIVE_DECODES"] == 1
    assert read(store, 200)[0] is ch_new and read(store, 201)[2]


def test_two_sessions_a_pinned_transaction_beside_fresh_statements():
    a = Session()
    a.execute("create table t (id bigint primary key, v bigint)")
    a.execute("insert into t values (1, 10), (2, 20)")
    b = Session(store=a.store, catalog=a.catalog)
    a.execute("begin")
    assert a.execute("select count(*) from t").rows == [[Datum.i64(2)]]
    b.execute("insert into t values (3, 30)")
    with Moved() as m:
        assert a.execute("select id from t where v > 0 order by id").rows == [[Datum.i64(1)], [Datum.i64(2)]]
        assert [r[0].val for r in b.execute("select id from t where v > 0 order by id").rows] == [1, 2, 3]
        assert [r[0].val for r in b.execute("select id from t where v > 0 order by id").rows] == [1, 2, 3]
        assert a.execute("select id from t where v > 0 order by id").rows == [[Datum.i64(1)], [Datum.i64(2)]]
    a.execute("commit")
    assert [r[0].val for r in a.execute("select id from t where v > 0 order by id").rows] == [1, 2, 3]
    # a's two reads decode at its own snapshot; b's first decodes and files, its second is answered above the decode
    assert m.by["COP_DECODE_MISSES"] == 3 and m.by["COP_DECODE_HITS"] + m.by["COP_CACHE_HITS"] == 1


def test_a_write_between_the_version_snapshot_and_the_filing_is_not_filed(monkeypatch):
    store = fill()
    decode = store._decode_region

    def decode_then_write(*args):
        ch = decode(*args)
        store.put_row(TID, 64, [1], [Datum.i64(192)], ts=110)   # lands after the scan, before the filing
        return ch

    monkeypatch.setattr(store, "_decode_region", decode_then_write)
    ch, batch, hit = read(store, 100)
    monkeypatch.undo()
    assert not hit and vals(ch) == [h * 3 for h in range(64)] and batch is not None
    assert len(store._chunk_cache) == len(store._batch_cache) == 0 and store._batch_cache.used == 0
    assert vals(read(store, 200)[0])[-1] == 192


def test_a_half_applied_commit_is_not_filed():
    """The kv already holds a version above the reader's start_ts while the
    write version has not moved yet: the all-seeing test refuses."""
    store = fill()
    store.kv.put(tablecodec.encode_row_key(TID, 64), store._row_encoder.encode([1], [Datum.i64(192)]), 150)
    read(store, 100)
    assert len(store._chunk_cache) == 0
    assert len(vals(read(store, 160)[0])) == 65 and len(store._chunk_cache) == 1


def test_a_commit_applied_but_not_yet_counted_refuses_the_hit():
    """The other half of the same window: the kv holds a version above the
    ENTRY's start_ts while the write version has not moved. A snapshot
    drawn now sees that version; the entry does not, so it may not answer."""
    store = fill()
    ch1, b1, _ = read(store, 100)
    assert read(store, 120)[2]
    store.kv.put(tablecodec.encode_row_key(TID, 64), store._row_encoder.encode([1], [Datum.i64(192)]), 150)
    with Moved() as m:
        ch2, b2, hit2 = read(store, 160)
        ch3, _, hit3 = read(store, 140)      # a snapshot below the commit misses too: one rule, no second look
    assert (hit2, hit3) == (False, False) and ch2 is not ch1 and b2 is not b1
    assert vals(ch2) == vals(ch1) + [192] and vals(ch3) == vals(ch1) and m.by["NATIVE_DECODES"] == 2
    resp = store.coprocessor(CopRequest(DAG, FULL, 161, region(store).region_id, region(store).epoch))
    assert resp.chunk.num_rows() == 65


def test_a_statement_between_a_commits_apply_and_its_version_bump_sees_the_commit():
    """txn.commit applies the rows, delivers them, and only then bumps the
    write version. The failpoint sits in between: a statement whose
    start_ts is drawn there is past the commit_ts and must read the row,
    through every cache, and a later read in the same snapshot agrees."""
    from tidb_tpu.util import failpoint

    a = Session()
    a.execute("create table t (id bigint primary key, v bigint)")
    a.execute("insert into t values (1, 10), (2, 20)")
    b = Session(store=a.store, catalog=a.catalog)
    q = "select id from t where v > 0 order by id"
    assert [r[0].val for r in b.execute(q).rows] == [1, 2]
    assert [r[0].val for r in b.execute(q).rows] == [1, 2]      # resident now: result, chunk and batch
    seen = []

    def between():
        failpoint.disable("store/before-bump-write-ver")        # the reads below must not come back here
        b.execute("begin")
        seen.append([r[0].val for r in b.execute(q).rows])
        seen.append([r[0].val for r in b.execute("select id from t where v >= 0 order by id").rows])

    with failpoint.enabled("store/before-bump-write-ver", between):
        a.execute("insert into t values (3, 30)")
    seen.append([r[0].val for r in b.execute(q).rows])          # after the bump, same transaction: the same rows
    b.execute("commit")
    assert seen == [[1, 2, 3]] * 3


def test_an_epoch_change_misses():
    store = fill()
    ch1, _, _ = read(store, 100)
    store.cluster.split(tablecodec.encode_row_key(TID, 32))
    left, right = store.cluster.regions()
    with Moved() as m:
        lo = store._region_read(left, FULL, DAG, 101, device=True)
        hi = store._region_read(right, FULL, DAG, 101, device=True)
    assert not lo[2] and not hi[2] and m.by["NATIVE_DECODES"] == 2
    assert vals(lo[0]) + vals(hi[0]) == vals(ch1)
    assert store._region_read(left, FULL, DAG, 102, device=True)[2]


def test_the_byte_bounds_hold_and_evict_in_lru_order():
    store = fill()
    one = handle_ranges(TID, [(0, 7)])
    ch, b, _ = read(store, 100, one)
    store.evict_caches()
    store._DECODE_HOST_BYTES = 3 * ch.nbytes()      # room for three chunks ...
    store._device_budget = 2 * b.nbytes()            # ... and for two batches
    ranges = [handle_ranges(TID, [(8 * i, 8 * i + 7)]) for i in range(6)]
    with Moved() as m:
        for i, r in enumerate(ranges):
            read(store, 100 + i, r)
            assert store._chunk_cache.used <= store._DECODE_HOST_BYTES and store._batch_cache.used <= store._device_budget
    assert len(store._chunk_cache) == 3 and len(store._batch_cache) == 2
    assert m.by["COP_DECODE_EVICTIONS"] == 3 + 4 and m.by["COP_DECODE_DEVICE_BYTES"] == 2 * b.nbytes()
    with Moved() as m:
        assert [read(store, 200, r)[2] for r in ranges[4:]] == [True, True]          # the two newest: chunk and batch
        assert store.region_chunk(region(store), ranges[3], DAG, 200) is not None    # the third chunk, and its place refreshed
        read(store, 201, ranges[0], device=False)                                    # a new chunk: the oldest (4) leaves
    assert (m.by["COP_DECODE_HITS"], m.by["COP_DECODE_MISSES"], m.by["COP_DECODE_EVICTIONS"]) == (3, 1, 1)
    with Moved() as m:
        assert store.region_chunk(region(store), ranges[3], DAG, 202) is not None
        assert store.region_chunk(region(store), ranges[5], DAG, 202) is not None
    assert m.by["COP_DECODE_HITS"] == 2
    with Moved() as m:
        store.region_chunk(region(store), ranges[4], DAG, 203)
    assert m.by["COP_DECODE_MISSES"] == 1


def test_evict_caches_empties_both_and_returns_the_gauge():
    g0 = metrics.COP_DECODE_DEVICE_BYTES.value
    store = fill()
    ch, b, _ = read(store, 100)
    store.coprocessor(CopRequest(DAG, FULL, 101, region(store).region_id, region(store).epoch))
    assert metrics.COP_DECODE_DEVICE_BYTES.value == g0 + b.nbytes() and len(store._cop_cache) == 1
    assert store.evict_caches() >= ch.nbytes()
    assert len(store._chunk_cache) == len(store._batch_cache) == len(store._cop_cache) == 0
    assert store._chunk_cache.used == store._batch_cache.used == 0 and metrics.COP_DECODE_DEVICE_BYTES.value == g0
    with Moved() as m:
        assert not read(store, 102)[2]
    assert m.by["NATIVE_DECODES"] == 1


def run_threads(targets, timeout=120.0):
    """Start, join with a timeout, and see that every thread ended; the
    interpreter switches threads every 10 us meanwhile."""
    threads = [threading.Thread(target=t) for t in targets]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)


def test_eight_threads_reading_one_key_get_equal_answers_and_leave_one_entry():
    store = fill(256)
    start = threading.Barrier(8)
    got = [None] * 8

    def reader(i):
        start.wait(60)
        ch, batch, _ = read(store, 100 + i)
        got[i] = (vals(ch), int(batch.n_rows))

    run_threads([lambda i=i: reader(i) for i in range(8)])
    assert all(g == ([h * 3 for h in range(256)], 256) for g in got)
    assert len(store._chunk_cache) == len(store._batch_cache) == 1
    ch, batch, hit = read(store, 200)
    assert hit and store._batch_cache.used == batch.nbytes() and store._chunk_cache.used == ch.nbytes()


def test_readers_beside_a_writer_never_see_a_row_of_the_future_and_the_accounts_balance():
    """Sixteen readers over four ranges under a small device budget while a
    writer commits rows: a reader at ts sees exactly the rows committed at
    or below ts, and at the end the caches' byte counts and the gauge are
    what the resident entries add up to."""
    g0 = metrics.COP_DECODE_DEVICE_BYTES.value
    store = fill(32)
    ranges = [handle_ranges(TID, [(0, 15)]), handle_ranges(TID, [(16, 31)]), handle_ranges(TID, [(0, 31)]), FULL]
    store._device_budget = 3 * read(store, 20, FULL)[1].nbytes()
    commits = [(1000 + 10 * k, 32 + k) for k in range(12)]   # (commit ts, handle)
    wrong = []

    def writer():
        for ts, h in commits:
            store.put_row(TID, h, [1], [Datum.i64(h * 3)], ts=ts)

    def reader(i):
        for n in range(40):
            ts = 995 + 7 * ((i + n) % 20)
            ch, batch, _ = read(store, ts, FULL)
            want = [h * 3 for h in range(32)] + [h * 3 for c, h in commits if c <= ts]
            # a commit at or below ts that has not landed yet may be missing; one above ts may never show
            if not set(vals(ch)) <= set(want) or int(batch.n_rows) != ch.num_rows():
                wrong.append((ts, vals(ch)))
            read(store, ts, ranges[(i + n) % 3])

    run_threads([writer] + [lambda i=i: reader(i) for i in range(16)])
    assert not wrong, wrong[:2]
    assert vals(read(store, 2000)[0]) == [h * 3 for h in range(44)]
    with store._cop_lock:
        chunks = [v for v, _ts, _c in store._chunk_cache._entries.values()]
        batches = [v for v, _ts, _c in store._batch_cache._entries.values()]
        assert store._chunk_cache.used == sum(c.nbytes() for c in chunks)
        assert store._batch_cache.used == sum(b.nbytes() for b in batches) <= store._device_budget
    assert metrics.COP_DECODE_DEVICE_BYTES.value == g0 + store._batch_cache.used
    store.evict_caches()
    assert metrics.COP_DECODE_DEVICE_BYTES.value == g0


def test_the_cop_request_reports_hit_and_rows_and_records_read_flow_on_a_hit():
    from tidb_tpu.util import tracing

    store = fill()
    r = region(store)
    store.pd.flow.heartbeat()   # drain the load's deltas
    seen = []
    for ts in (100, 101):
        with store._cop_lock:
            store._cop_cache.clear()   # the result cache would answer above the decode
        with tracing.trace("t") as root:
            resp = store.coprocessor(CopRequest(DAG, FULL, ts, r.region_id, r.epoch))
        assert resp.other_error is None and resp.chunk.num_rows() == 64
        (span,) = root.find("cop.decode")
        (beat,) = [b for b in store.pd.flow.heartbeat() if b.region_id == r.region_id]
        seen.append((span.attrs["hit"], span.attrs["rows"], beat.read_keys, beat.read_bytes == resp.exec_summaries[0].num_bytes))
    assert seen == [(False, 64, 64, True), (True, 64, 64, True)]


@pytest.mark.parametrize("case", ["over_budget", "earlier_stays", "older_misses", "commit_above_misses", "lru", "clear"])
def test_the_snapshot_cache_alone(case):
    committed = [0]
    c = _SnapshotCache(lambda: committed[0], metrics.COP_DECODE_DEVICE_BYTES)
    g0 = metrics.COP_DECODE_DEVICE_BYTES.value
    if case == "over_budget":      # serves its own request, is not kept, evicts nothing
        assert c.put("a", 1, 10, cost=4, budget=8) == [] and c.put("b", 2, 10, cost=9, budget=8) == []
        assert len(c) == 1 and c.used == 4 and c.get("b", 10) is None
    elif case == "earlier_stays":  # racing readers of one key: the earlier snapshot's entry stays
        c.put("a", "first", 10, 4, 8)
        c.put("a", "second", 12, 4, 8)
        assert c.get("a", 11) == "first" and c.used == 4
        c.put("a", "third", 9, 5, 8)
        assert c.get("a", 9) == "third" and c.used == 5
    elif case == "older_misses":
        c.put("a", 1, 10, 1, 8)
        assert c.get("a", 9) is None and c.get("a", 10) == 1 and c.get("b", 10) is None
    elif case == "commit_above_misses":   # a version committed above the entry's start_ts: no snapshot may take it
        c.put("a", 1, 10, 1, 8)
        committed[0] = 10
        assert c.get("a", 12) == 1
        committed[0] = 11
        assert c.get("a", 12) is None and c.get("a", 10) is None and len(c) == 1
    elif case == "lru":
        for k in "abc":
            c.put(k, k, 10, 3, 9)
        assert c.get("a", 10) == "a"            # a hit refreshes the entry's place
        assert c.put("d", "d", 10, 6, 9) == ["b", "c"] and c.used == 9
        assert c.get("a", 10) == "a" and c.get("d", 10) == "d"
    else:
        c.put("a", 1, 10, 3, 9)
        c.put("b", 2, 10, 3, 9)
        assert sorted(c.clear()) == [1, 2] and len(c) == 0 and c.used == 0
    assert metrics.COP_DECODE_DEVICE_BYTES.value == g0 + c.used
    c.clear()
