"""Percolator transaction engine over MemKV (ref: unistore/tikv/mvcc.go
MVCCStore prewrite/commit + lockstore; client-go 2PC driver;
pkg/store/driver/txn/txn_driver.go; TiKV's lock manager waiter queue and
deadlock detector).

The reference splits 2PC across the client (primary selection, parallel
prewrite, commit point) and the store (lock CF, write CF, conflict checks).
In one process both halves collapse into this engine:

  prewrite   lock every mutated key after write-conflict + lock checks
  commit     apply buffered values at commit_ts, release locks (atomic
             under the engine mutex — readers never observe a partial
             commit, which is why snapshot reads here do not need the
             reference's lock-wait/resolve path)
  rollback   drop this txn's locks
  pessimistic lock
             intention locks taken at DML time (ref: acquire pessimistic
             lock, mvcc.go; lock converts to a prewrite lock at commit)

A caller that may wait (`wait_s` > 0: the session's
innodb_lock_wait_timeout) waits for a key another transaction holds, on
the engine mutex's condition, which every release of locks notifies; the
wait-for edges (waiter start_ts -> holder start_ts) are kept meanwhile, so
a wait that would close a cycle is refused at once.

Failure semantics match Percolator where observable in-process:
  KeyIsLocked    another live txn holds the key, and the caller does not
                 wait (`wait_s` 0) or its wait ran out (errno 1205)
  Deadlock       the holder waits, through others, for this txn (1213)
  WriteConflict  a commit landed after this txn's snapshot/for_update ts
"""

from __future__ import annotations

import threading
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from time import perf_counter_ns

from ..util import metrics, tracing
from .kv import MemKV


class TxnError(Exception):
    pass


class KeyIsLocked(TxnError):
    def __init__(self, key: bytes, holder_ts: int):
        super().__init__(f"key is locked by txn {holder_ts}")
        self.key, self.holder_ts = key, holder_ts


class Deadlock(TxnError):
    def __init__(self, key: bytes, holder_ts: int, start_ts: int):
        super().__init__(f"deadlock: txn {start_ts} would wait for txn {holder_ts}, which waits for it")
        self.key, self.holder_ts, self.start_ts = key, holder_ts, start_ts


class WriteConflict(TxnError):
    def __init__(self, key: bytes, conflict_ts: int, start_ts: int):
        super().__init__(
            f"write conflict: key committed at {conflict_ts} > txn start {start_ts}"
        )
        self.key, self.conflict_ts, self.start_ts = key, conflict_ts, start_ts


@dataclass
class Lock:
    """(ref: lockstore entry / kvrpcpb.LockInfo)."""

    primary: bytes
    start_ts: int
    op: str  # "prewrite" | "pessimistic"
    value: bytes | None = None  # buffered write (prewrite only)
    is_delete: bool = False
    for_update_ts: int = 0


class TxnEngine:
    def __init__(self, kv: MemKV, on_commit=None, on_apply=None,
                 pre_apply=None, write_guard=None, on_apply_group=None):
        self.kv = kv
        self.locks: dict[bytes, Lock] = {}  # guarded_by: _mu
        self._mu = threading.RLock()
        self._released = threading.Condition(self._mu)  # notified whenever locks leave the table
        self._waits: dict[int, int] = {}  # waiter start_ts -> holder start_ts; guarded_by: _mu
        self._on_commit = on_commit  # store cache-invalidation hook
        self._on_apply = on_apply  # batch hook: ([(key, value|None,
        # prev_live)], commit_ts) called AFTER the kv critical section
        # (PD write flow + replication proposal + CDC delivery)
        self._on_apply_group = on_apply_group  # group-commit hook:
        # ([(applied, commit_ts)]) for a whole coalesced window at once,
        # so the store can fold every lane's changes into ONE replication
        # proposal per region (falls back to per-lane _on_apply when unset)
        self._pre_apply = pre_apply  # keys hook BEFORE any apply: may raise
        # (the store's write-quorum gate — a refused commit applies nothing)
        self._write_guard = write_guard  # zero-arg ctx factory wrapping
        # [commit-ts draw .. change delivery]: the CDC resolved-ts sampler
        # treats the window as an in-flight write (cdc/hub.py WriteGuard)

    def _guard(self):
        return self._write_guard() if self._write_guard is not None else nullcontext()

    # ------------------------------------------------------------------
    def _blocker(self, keys, start_ts: int):  # requires: _mu
        """(key, Lock) of the first key another transaction holds, or None."""
        for k in keys:
            l = self.locks.get(k)
            if l is not None and l.start_ts != start_ts:
                return k, l
        return None

    def _waits_for(self, holder_ts: int, start_ts: int) -> bool:  # requires: _mu
        """Whether `holder_ts` waits, directly or through others, for `start_ts`."""
        seen = set()
        while holder_ts in self._waits and holder_ts not in seen:
            seen.add(holder_ts)
            holder_ts = self._waits[holder_ts]
            if holder_ts == start_ts:
                return True
        return False

    def _wait_unlocked(self, keys, start_ts: int, wait_s: float, sp=None) -> None:  # requires: _mu
        """Return once no key of `keys` is held by another transaction,
        waiting on `_released` (the mutex is let go meanwhile).  Raises
        KeyIsLocked where `wait_s` is 0 or once `wait_s` seconds have
        passed, Deadlock where the holder waits for this transaction.
        A wait is counted, and put on `sp` as `waited`, `wait_ms`."""
        blocked = self._blocker(keys, start_ts)
        if blocked is None:
            return
        if wait_s <= 0:
            raise KeyIsLocked(blocked[0], blocked[1].start_ts)
        metrics.TXN_LOCK_WAITS.inc()
        t0 = perf_counter_ns()
        deadline = t0 + int(wait_s * 1e9)
        try:
            while blocked is not None:
                key, holder = blocked[0], blocked[1].start_ts
                if self._waits_for(holder, start_ts):
                    metrics.TXN_DEADLOCKS.inc()
                    raise Deadlock(key, holder, start_ts)
                left = deadline - perf_counter_ns()
                if left <= 0:
                    metrics.TXN_LOCK_WAIT_TIMEOUTS.inc()
                    raise KeyIsLocked(key, holder)
                self._waits[start_ts] = holder
                try:
                    self._released.wait(left / 1e9)
                finally:
                    del self._waits[start_ts]
                blocked = self._blocker(keys, start_ts)
        finally:
            waited = perf_counter_ns() - t0
            metrics.TXN_LOCK_WAIT_NS.inc(waited)
            if sp is not None:
                sp.attrs.update(waited=True, wait_ms=round(waited / 1e6, 3))

    def acquire_pessimistic(self, keys: list, primary: bytes, start_ts: int, for_update_ts: int,
                            wait_s: float = 0.0) -> int:
        """Intention locks for pessimistic DML (ref: mvcc.go pessimistic
        lock path), held until commit/rollback; a key another transaction
        holds is waited for (`_wait_unlocked`).  Returns the newest commit
        ts above `for_update_ts` among the keys, 0 where there is none: the
        locks are taken either way (TiKV's lock with conflict), so the
        caller can read those rows again at a newer for_update_ts under
        them (TiDB's pessimistic retry)."""
        with tracing.span("txn.lock", keys=len(keys), waited=False, wait_ms=0.0, outcome="locked") as sp:
            with self._mu:
                try:
                    self._wait_unlocked(keys, start_ts, wait_s, sp)
                except TxnError as exc:
                    if sp is not None:
                        sp.set("outcome", "deadlock" if isinstance(exc, Deadlock) else "timeout")
                    raise
                newer = max((self.kv.latest_ts(k) for k in keys), default=0)
                for k in keys:
                    if k not in self.locks:
                        self.locks[k] = Lock(primary, start_ts, "pessimistic", for_update_ts=for_update_ts)
        return newer if newer > for_update_ts else 0

    def prewrite(self, mutations: dict, primary: bytes, start_ts: int, conflict_ts: int | None = None,
                 wait_s: float = 0.0):
        """mutations: key -> value bytes (None = delete tombstone).  A key
        another transaction holds is waited for as `acquire_pessimistic`
        waits.  A key without this transaction's pessimistic lock conflicts
        with a commit above `conflict_ts` (default `start_ts`; a pessimistic
        transaction passes its newest for_update_ts: every row key it writes
        is locked, and every index key carries a locked row's handle)."""
        bound = start_ts if conflict_ts is None else conflict_ts
        with tracing.span("txn.prewrite", keys=len(mutations)):
            with self._mu:
                self._wait_unlocked(mutations, start_ts, wait_s)
                for k in mutations:
                    l = self.locks.get(k)
                    if l is not None and l.op == "pessimistic":
                        continue  # read at its for_update_ts under the lock
                    cts = self.kv.latest_ts(k)
                    if cts > bound:
                        raise WriteConflict(k, cts, bound)
                for k, v in mutations.items():
                    self.locks[k] = Lock(primary, start_ts, "prewrite", v, v is None)

    def commit(self, keys: list, start_ts: int, commit_ts):
        """commit_ts: an int, or a callable TSO source. When callable, the
        timestamp is drawn INSIDE the kv critical section: with a monotone
        TSO, no reader can have obtained read_ts >= commit_ts before the
        whole apply is visible — snapshot isolation without the reference's
        lock-wait/resolve read path. Returns the commit_ts used."""
        applied = []
        with tracing.span("txn.commit", keys=len(keys)):
            with self._guard():  # entered BEFORE the commit ts is drawn
                with self._mu:
                    staged = []
                    for k in keys:
                        l = self.locks.get(k)
                        if l is None or l.start_ts != start_ts:
                            raise TxnError(f"lock not found for commit (txn {start_ts})")
                        if l.op != "prewrite":
                            raise TxnError("commit before prewrite (pessimistic lock not converted)")
                        staged.append((k, l))
                    if self._pre_apply is not None and staged:
                        # the write-quorum gate: raises BEFORE anything applies,
                        # so a quorum-lost region refuses the whole commit (the
                        # caller's locks stay put for its rollback path)
                        self._pre_apply([k for k, _ in staged])
                    with self.kv.lock:  # readers see all of the commit or none
                        if callable(commit_ts):
                            commit_ts = commit_ts()
                        for k, l in staged:
                            v = None if l.is_delete else l.value
                            prev = self.kv.put(k, v, commit_ts)
                            del self.locks[k]
                            applied.append((k, v, prev))
                    self._released.notify_all()
                if self._on_apply is not None and applied:
                    self._on_apply(applied, commit_ts)  # outside the locks —
                    # flow bookkeeping must never extend the window in which
                    # readers are blocked
            if self._on_commit is not None and staged:
                self._on_commit()
        return commit_ts

    def rollback(self, keys: list, start_ts: int):
        with self._mu:
            for k in keys:
                l = self.locks.get(k)
                if l is not None and l.start_ts == start_ts:
                    del self.locks[k]
            self._released.notify_all()

    def release_all(self, start_ts: int):
        """Drop every lock a txn holds (rollback convenience)."""
        with self._mu:
            for k in [k for k, l in self.locks.items() if l.start_ts == start_ts]:
                del self.locks[k]
            self._released.notify_all()

    # ------------------------------------------------------------------
    def commit_txn(self, mutations: dict, start_ts: int, commit_ts, conflict_ts: int | None = None,
                   wait_s: float = 0.0):
        """Full 2PC for an in-process txn: prewrite everything (primary =
        first key), then commit. Raises without side effects on conflict;
        pessimistic locks this txn already holds are converted.
        commit_ts may be a callable TSO source (see commit); `conflict_ts`
        and `wait_s` are prewrite's."""
        if not mutations:
            return None
        keys = list(mutations)
        primary = keys[0]
        try:
            self.prewrite(mutations, primary, start_ts, conflict_ts, wait_s)
        except TxnError:
            self.release_all(start_ts)
            raise
        return self.commit(keys, start_ts, commit_ts)

    def commit_group(self, reqs: list, tso) -> list:
        """Group commit (ISSUE 19): 2PC several independent autocommit
        transactions in ONE write-guard window and ONE kv critical
        section, each lane committing at its OWN timestamp drawn from
        `tso` in lane order. reqs: [(mutations dict, start_ts)]. Returns
        one result per lane: the commit_ts on success, or the exception
        instance for a lane that fell out (conflict / refused quorum —
        its locks are released; the window stands for the other lanes).
        The per-lane sequence is exactly commit_txn's — prewrite, quorum
        gate, apply, release — so a group of one is byte-equivalent to
        the single path."""
        results: list = [None] * len(reqs)
        staged_lanes: list = []  # (idx, keys, start_ts)
        applied_lanes: list = []  # (applied, commit_ts)
        with self._guard():  # entered BEFORE any commit ts is drawn
            with self._mu:
                for i, (mutations, start_ts) in enumerate(reqs):
                    if not mutations:
                        continue
                    keys = list(mutations)
                    try:
                        self.prewrite(mutations, keys[0], start_ts)
                        if self._pre_apply is not None:
                            self._pre_apply(keys)
                    except Exception as exc:  # TxnError | QuorumLostError
                        self.release_all(start_ts)
                        results[i] = exc
                        continue
                    staged_lanes.append((i, keys, start_ts))
                with self.kv.lock:  # readers see all of a lane or none
                    for i, keys, start_ts in staged_lanes:
                        cts = tso()
                        applied = []
                        for k in keys:
                            l = self.locks[k]
                            v = None if l.is_delete else l.value
                            prev = self.kv.put(k, v, cts)
                            del self.locks[k]
                            applied.append((k, v, prev))
                        results[i] = cts
                        applied_lanes.append((applied, cts))
                self._released.notify_all()
            if applied_lanes:  # outside the locks, inside the guard —
                # same bracket as the single path's _on_apply
                if self._on_apply_group is not None:
                    self._on_apply_group(applied_lanes)
                elif self._on_apply is not None:
                    for applied, cts in applied_lanes:
                        self._on_apply(applied, cts)
        if applied_lanes and self._on_commit is not None:
            self._on_commit()
        return results

    def check_unlocked(self, keys, start_ts: int = 0):
        """Raise KeyIsLocked if any key is held by another transaction —
        the guard bulk ingest (LOAD DATA, BR restore) runs before writing
        around the lock table (ref: Lightning conflict with live txns)."""
        with self._mu:
            for k in keys:
                l = self.locks.get(k)
                if l is not None and l.start_ts != start_ts:
                    raise KeyIsLocked(k, l.start_ts)

    @contextmanager
    def ingest_guard(self):
        """One critical section for a whole bulk-import batch: the caller
        draws its read/write timestamps, re-runs its duplicate checks, and
        applies the writes all inside — no committed write or prewrite can
        interleave (LOAD DATA / BR restore vs in-flight 2PC; lock order
        engine _mu -> kv.lock matches commit())."""
        with self._mu:
            with self.kv.lock:
                yield

    def bulk_ingest(self, items, ts: int):
        """Atomically verify-and-apply (key, value) pairs (BR restore —
        no value-level duplicate checks needed; LOAD DATA wraps its whole
        check+apply in ingest_guard instead)."""
        applied = []
        with self._guard():
            with self.ingest_guard():
                self.check_unlocked([k for k, _ in items])
                if self._pre_apply is not None and items:
                    self._pre_apply([k for k, _ in items])
                for k, v in items:
                    applied.append((k, v, self.kv.put(k, v, ts)))
            if self._on_apply is not None and applied:
                self._on_apply(applied, ts)
