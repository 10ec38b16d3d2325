"""The TPU coprocessor store — this framework's unistore.

Implements the coprocessor contract end to end
(ref: unistore/tikv/server.go:625 Coprocessor ->
cophandler/cop_handler.go:89 HandleCopRequest): a CopRequest carries the DAG,
key ranges and snapshot ts; the store materializes the region's rows as a
columnar chunk (rowcodec decode happens ONCE per region version, then the
chunk — host and device — is cached), runs the fused device program, and
returns the result chunk plus execution summaries.

Region errors (epoch mismatch after a split) surface exactly like TiKV's so
the distsql layer exercises the same retry/re-split path as the reference
(ref: copr/coprocessor.go:1424).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from dataclasses import dataclass, field, replace

from ..chunk import Chunk, to_device_batch
from ..chunk.device import DeviceBatch, to_stacked_device_batch
from ..codec import tablecodec
from ..codec.rowcodec import RowEncoder, decode_row_to_datum_map, fill_origin_default
from ..exec.builder import DEFAULT_GROUP_CAPACITY, ProgramCache
from ..exec.dag import DAGRequest
from ..exec.executor import OverflowRetryError, drive_batched_program_info, drive_program_info, run_dag_reference, _pow2
from ..exec.launch import upload_transfers
from ..types import Datum
from .kv import MemKV
from .region import Cluster, Region


# The least capacity a region's batch is uploaded at: a batch is sized at
# its rows' power of two, and a program is compiled per capacity, so without
# a floor a range that runs off its table's end (fewer rows than it asked
# for) met rung 64, 32, 16, ... and compiled it where it was served.
# sysbench's ranges of 100 rows are on rung 128 already, and every table of
# the TPC-H cells has more rows than this.  A join's build side keeps its own
# power of two: the join kernels choose their strategy by the two sides'
# capacities.
MIN_BATCH_ROWS = 128


def batch_rung(rows: int) -> int:
    """The capacity a region batch of `rows` rows is uploaded at."""
    return _pow2(max(rows, MIN_BATCH_ROWS))


@dataclass(frozen=True)
class KeyRange:
    """(ref: coprocessor.KeyRange)."""

    start: bytes
    end: bytes


@dataclass
class CopRequest:
    """(ref: coprocessor.Request: tp=DAG, data, ranges, start_ts).

    aux_chunks: broadcast operands for the DAG's join build sides, one per
    non-probe scan in canonical order (the TiFlash broadcast-exchange analog
    — ref: mpp_exec.go:669 Broadcast partition mode). Every region task of a
    broadcast join carries the same chunks; the device upload is shared.

    paging_size: when set, the scan stops after at most this many rows and
    the response carries `last_range`, the resume cursor for the next page
    (ref: copr/coprocessor.go:1393 handleCopPagingResult; store side
    cop_handler.go:210 lastRange). Row-local DAGs only — aggregations
    cannot produce correct partials from a partial scan."""

    dag: DAGRequest
    ranges: list
    start_ts: int
    region_id: int = 0
    region_epoch: int = 0
    aux_chunks: list = field(default_factory=list)
    paging_size: int | None = None
    small_groups: int | None = None  # planner NDV hint (stats-driven)
    peer_store: int = -1  # the peer the client routed to (-1 = whoever
    # leads at serve time); a non-leader peer answers NotLeader unless
    # replica_read (ref: kvrpcpb.Context.peer)
    replica_read: bool = False  # follower read: a non-leader peer may
    # serve IF its safe_ts covers start_ts, else DataIsNotReady
    # (ref: kvrpcpb.Context.replica_read)
    mesh: bool = False  # the dispatch planner chose the MESH tier for this
    # store batch: shard the stacked lanes over the device mesh and merge
    # the per-region partial states on device (psum over the region axis)
    # instead of returning R per-region partials (distsql/planner.py)
    mesh_min_rows: int = 0  # tidb_tpu_mesh_min_rows carried to the store:
    # the AUTHORITATIVE data-size floor, applied to the group's actually
    # decoded row total (the client's estimate only gated the attempt)
    whole_dag: DAGRequest | None = None  # the statement's UNSPLIT DAG, of
    # which `dag` is the pushdown half: the root's half rides the request.
    # The dispatch attaches it only where this store will hold the
    # statement's ONE state: to a request's lone cop task, which then runs
    # it over the region's batch as one program, and to the lanes of a mesh
    # group that is the whole request, whose program goes on through the
    # root's half behind its on-device merge. The response says so
    # (`root_fused`); a store that serves a request split takes the field
    # off first, so `_cop_cache_key` files every answer under what ran


@dataclass
class ExecSummary:
    """(ref: tipb.ExecutorExecutionSummary, cop_handler.go:518). Extended
    with device-time attribution: where the task's wall time went —
    XLA compile (vs. a program-cache hit) and the bytes the executor
    moved (scan row: decoded region bytes; final row: result bytes)."""

    time_processed_ns: int = 0
    num_produced_rows: int = 0
    num_iterations: int = 1
    time_compile_ns: int = 0  # 0 on a cache hit
    cache_hit: bool = False  # the fused program came from the cache
    num_bytes: int = 0
    # radix-join attribution (ISSUE 13): set on Join executors whose task
    # rode the radix-partitioned kernel — partition count, the join
    # capacity RUNG the program compiled at, and the skew-escape row
    # count; 0/0/0 = monolithic kernel (EXPLAIN ANALYZE `join_radix` row)
    radix_partitions: int = 0
    radix_rung: int = 0
    radix_escapes: int = 0


@dataclass
class CopResponse:
    chunk: Chunk | None = None
    region_error: str | None = None
    other_error: str | None = None
    exec_summaries: list = field(default_factory=list)
    last_range: list | None = None  # [KeyRange] resume cursor; None = drained
    batched: int = 0  # nonzero = served by a vmapped batch launch (NOT by
    # the cop cache, an overflow fall-out, or a single-path degrade); the
    # value identifies the launch within its batch_coprocessor call, so the
    # dispatch layer can count distinct launches for launches_saved
    mesh_merged: int = 0  # nonzero = this lane's partial state was merged
    # ON DEVICE with its group's other lanes (psum over the region axis);
    # the value is the number of lanes the one merged state covers — the
    # group's FIRST lane carries the merged chunk, the rest answer empty
    root_fused: bool = False  # the program that answered ran the request's
    # `whole_dag`: the chunk holds the statement's rows (the carrier lane's,
    # in a mesh group) and the root has nothing left to merge


def _apply_radix_attribution(summaries: list, walk, info) -> None:
    """Fold the driver's `join_radix` attribution (exec/executor.py
    _radix_attribution: partitions / capacity rung / skew escapes) onto
    the FIRST Join executor's summary — the triple is PROGRAM-level (one
    escape total, one plan per compiled program), so stamping every Join
    would multiply it in EXPLAIN ANALYZE's cross-summary sum; the summary
    indexes align with the executor walk, same as the row counts."""
    ri = info.get("radix") if isinstance(info, dict) else None
    if not ri:
        return
    from ..exec.dag import Join as _Join

    for i, ex in enumerate(walk):
        if isinstance(ex, _Join) and i < len(summaries):
            summaries[i].radix_partitions = int(ri.get("partitions") or 0)
            summaries[i].radix_rung = int(ri.get("rung") or 0)
            summaries[i].radix_escapes = int(ri.get("escapes") or 0)
            return


class _SnapshotCache:
    """What the store read from regions at one data version, oldest use
    first under a budget: whole cop responses (the budget counts entries),
    decoded chunks (host bytes) and their device batches (device bytes).

    The snapshot rule of all three, in its two halves. `TPUStore._may_file`
    lets an entry be filed only for a snapshot that saw every committed
    version, the store's write version being the one taken before the region
    was read; the entry records that start_ts. `get` then answers a request
    whose key matches (region, epoch, write version, what was read) only at
    start_ts >= the entry's: with the write version unchanged such a
    snapshot sees byte-identical data, while an OLDER one might predate a
    version the entry includes and must miss (ref:
    pkg/store/copr/coprocessor_cache.go keying responses by region data
    version). And only while no version is committed above the entry's
    start_ts: a commit's rows are in the kv before its bump of the write
    version (txn.commit applies, delivers, then bumps), and a snapshot drawn
    in between sees them where the entry does not.

    Not locked: every method is called under the store's `_cop_lock`, which
    also guards the write version (`get` takes kv.lock inside it for
    max_committed: the one-way order `_may_file` names)."""

    __slots__ = ("_entries", "used", "_max_committed", "_gauge")

    def __init__(self, max_committed, gauge=None):
        self._entries: dict = {}  # key -> (value, entry start_ts, cost)
        self.used = 0  # sum of the entries' costs
        self._max_committed = max_committed  # () -> the kv's newest commit ts
        self._gauge = gauge  # follows `used`, summed over the process's stores

    def _account(self, delta: int) -> None:
        self.used += delta
        if self._gauge is not None and delta:
            self._gauge.inc(delta)

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key, start_ts: int):
        ent = self._entries.get(key)
        if ent is None or start_ts < ent[1] or self._max_committed() > ent[1]:
            return None
        self._entries[key] = self._entries.pop(key)  # refresh LRU position
        return ent[0]

    def put(self, key, value, start_ts: int, cost: int, budget: int) -> list:
        """File `value`, then make room. Returns the values that left, for
        the caller to count and to let go of outside the lock. A value
        over the whole budget is not kept: it serves its own request."""
        if cost > budget:
            return []
        old = self._entries.pop(key, None)
        if old is not None and old[1] <= start_ts:
            self._entries[key] = old  # racing readers of one key: the earlier snapshot's stays
            return []
        self._entries[key] = (value, start_ts, cost)
        delta = cost - (old[2] if old is not None else 0)
        gone = []
        while self.used + delta > budget:
            evicted, _ts, c = self._entries.pop(next(iter(self._entries)))
            delta -= c
            gone.append(evicted)
        self._account(delta)
        return gone

    def clear(self) -> list:
        """Empty the cache; returns the values it held."""
        gone = [v for v, _ts, _cost in self._entries.values()]
        self._entries.clear()
        self._account(-self.used)
        return gone


def _fault_matches(value, store_id: int) -> bool:
    """Per-store failpoint arming: True fires for every store; a
    set/list/tuple of ids fires for those stores; a dict
    `{"stores": ids-or-None, ...}` fires for the listed stores (None =
    all) and may carry extra payload (`backoff_ms` for server-busy); a
    ZERO-arg callable returns any of those shapes per hit (custom
    fire-N-times logic — `failpoint.eval` already invokes callables with
    no arguments, so this is the only callable arity that exists; a
    value arriving un-invoked via `failpoint.peek` is asked here).
    None/falsy never fires."""
    if not value:
        return False
    if callable(value):  # peek path hands over the raw callable
        return _fault_matches(value(), store_id)
    if value is True or isinstance(value, int):
        return True
    if isinstance(value, (set, frozenset, list, tuple)):
        return store_id in value
    if isinstance(value, dict):
        stores = value.get("stores")
        return stores is None or store_id in stores
    return True


class TPUStore:
    """KV + regions + TPU coprocessor, one process (ref: mockstore
    EmbedUnistore, mockstore.go:86)."""

    def __init__(self):
        from ..pd.core import PlacementDriver
        from ..replication import ReplicaManager
        from .txn import TxnEngine

        self.kv = MemKV()
        self.cluster = Cluster()
        self.programs = ProgramCache()
        # the control plane: flow stats always record (cheap increments);
        # the schedulers only act when tick()/timer runs (ref: every
        # TiKV store heartbeats PD whether or not PD is scheduling)
        self.pd = PlacementDriver(self)
        # the replication overlay: peer sets live on the cluster, per-peer
        # applied watermarks (safe_ts) live here; every committed write
        # proposes through it (ISSUE 8)
        self.replication = ReplicaManager(self)
        # change data capture (ISSUE 10): the hub subscribes to every
        # replication proposal; its WriteGuard brackets the write paths
        # so the resolved-ts frontier can prove quiescence
        from ..cdc import ChangefeedHub

        self.cdc = ChangefeedHub(self)
        # the HTAP columnar replica tier (ISSUE 12): per-table delta+stable
        # column stores fed by changefeeds, compacted by the pd.columnar
        # tick phase, routed to by tidb_isolation_read_engines
        from ..columnar import ColumnarReplica

        self.columnar = ColumnarReplica(self)
        self.txn = TxnEngine(self.kv, on_commit=self._bump_write_ver,
                             on_apply=self.record_applied_writes,
                             pre_apply=self._check_write_quorum,
                             write_guard=self.cdc.guard.writing,
                             on_apply_group=self.record_applied_writes_grouped)
        self._tso = itertools.count(100)  # guarded_by: _tso_lock
        self._tso_lock = threading.Lock()
        self._active_snapshots: dict[int, int] = {}  # guarded_by: _tso_lock
        self._write_ver = 0  # guarded_by: _cop_lock
        # what a region's rows decode to, host and device, keyed like the
        # result cache below by the region's data version and governed by
        # the same snapshot rule (_SnapshotCache)
        from ..util import metrics

        self._chunk_cache = _SnapshotCache(self.kv.max_committed)  # guarded_by: _cop_lock
        self._batch_cache = _SnapshotCache(self.kv.max_committed, metrics.COP_DECODE_DEVICE_BYTES)  # guarded_by: _cop_lock
        self._device_budget: int | None = None  # of _batch_cache; read off the device once
        self._aux_batch_cache: dict = {}  # token | (token, mesh devices) -> (chunk, DeviceBatch); guarded_by: _aux_lock
        self._build_side_cache: dict = {}  # the parts' tokens -> (parts, their concatenation); guarded_by: _aux_lock
        self._aux_lock = threading.Lock()  # select() fans tasks over threads
        self._chunk_tokens = itertools.count(1)  # monotonic chunk identity; guarded_by: _aux_lock
        # coprocessor RESULT cache (ref: pkg/store/copr/coprocessor_cache.go):
        # a whole region response keyed by the region's data version
        self._cop_cache = _SnapshotCache(self.kv.max_committed)  # guarded_by: _cop_lock
        self._cop_lock = threading.Lock()
        self._row_encoder = RowEncoder()
        # fault switches: logical placement stores marked down answer every
        # cop request with a typed StoreUnavailable region error (the
        # in-process analog of a TiKV store dropping off the network)
        self._down_stores: set[int] = set()  # guarded_by: _down_lock
        self._down_lock = threading.Lock()
        # per-store circuit breakers — client-side state, but shared by
        # every session/dispatch thread on this store (runtime import:
        # the distsql layer imports this module at load time)
        from ..distsql.dispatch import BreakerBoard

        self.breakers = BreakerBoard()
        # admission control (ISSUE 15): one gate per store — every session
        # and the dispatch layer of a server consult it; fully open by
        # default (0 = unlimited), configured by server config / tests
        # (runtime import: server/__init__ lazily re-exports, no cycle)
        from ..server.admission import AdmissionGate

        self.admission = AdmissionGate()
        # cross-session fused execution (ISSUE 19): one coalescer per
        # store — concurrent plan-cache-hit point-gets park in a
        # micro-batch window and ship as ONE batch-cop launch; concurrent
        # autocommit single-row writes fold into group commit (runtime
        # import for the same no-cycle reason as the gate)
        from ..server.coalesce import SessionCoalescer

        self.coalescer = SessionCoalescer(self)
        # point-in-time recovery (ISSUE 20): the ordered store-level log
        # of schema-change entries (the changefeed recovery source — they
        # are synthetic, never in KV) and the attached log backups
        # (dest uri -> br.pitr.LogBackup; GIL-atomic dict ops, written by
        # BACKUP LOG / stop, read by the pd.pitr tick)
        from ..cdc.schema import SchemaJournal

        self.schema_journal = SchemaJournal()
        self.log_backups: dict = {}

    # -- store fault switches (chaos/testing; ref: failpoint-driven store
    # outages in the reference's integration suites) ------------------------
    def set_down(self, store_id: int) -> None:
        """Take one logical placement store down: every cop request whose
        region is placed there answers `store_unavailable` until set_up."""
        with self._down_lock:
            self._down_stores.add(store_id)

    def set_up(self, store_id: int) -> None:
        with self._down_lock:
            self._down_stores.discard(store_id)

    def store_down(self, store_id: int) -> bool:
        with self._down_lock:
            return store_id in self._down_stores

    def down_stores(self) -> set:
        with self._down_lock:
            return set(self._down_stores)

    def ping_store(self, store_id: int) -> bool:
        """Store liveness probe (ref: client-go store liveness check /
        PD's store heartbeat watchdog): False when the store is switched
        down OR the unreachable failpoint is armed for it. Non-consuming —
        a probe must never eat a fire-N-times count."""
        from ..util import failpoint

        if self.store_down(store_id):
            return False
        return not _fault_matches(failpoint.peek("store/unreachable"), store_id)

    def evict_caches(self) -> int:
        """Empty the three version-keyed caches (cop responses, decoded
        region chunks, their device batches) and the aux-batch cache — the
        first OOM action in the chain (ref: pkg/util/memory ActionOnExceed
        SoftLimit/spill ordering: free reclaimable buffers before killing
        the query). The next read of any region scans, decodes and uploads
        again. Returns an approximate count of the host bytes freed."""
        with self._cop_lock:
            freed = self._chunk_cache.used
            responses, _batches = self._drop_version_caches()
        freed += sum(r.chunk.nbytes() for r, _flow in responses if r.chunk is not None)
        with self._aux_lock:  # select() uploads aux batches from pool threads
            self._aux_batch_cache.clear()
            self._build_side_cache.clear()
        return freed

    def _drop_version_caches(self) -> tuple:  # requires: _cop_lock
        """Empty the result cache and both decode caches. Returns the cop
        responses and the device batches they held: the caller lets go of
        them after the lock, which is where the batches' HBM is returned."""
        self._chunk_cache.clear()
        return self._cop_cache.clear(), self._batch_cache.clear()

    def next_ts(self) -> int:
        """Store-global TSO (ref: PD timestamp oracle; mock unistore/pd.go).
        Sessions sharing a store draw from one clock so snapshots and
        commit timestamps totally order across sessions."""
        with self._tso_lock:
            return next(self._tso)

    def advance_tso(self, ts: int) -> None:
        """Fast-forward the TSO past `ts` (the CDC replay sink's
        downstream clock sync: a mirror snapshot at a fresh TSO must see
        every replayed version at or below the source's resolved
        frontier). A no-op when the clock is already ahead."""
        with self._tso_lock:
            self._tso = itertools.count(max(next(self._tso), ts + 1))

    def register_snapshot(self, start_ts: int) -> None:
        """An open transaction pins its snapshot: GC never collects at or
        above the oldest registered start_ts (ref: the reference's
        min-start-ts reporting into PD's safepoint calculation,
        gc_worker.go calcSafePointByMinStartTS)."""
        with self._tso_lock:
            self._active_snapshots[start_ts] = self._active_snapshots.get(start_ts, 0) + 1

    def unregister_snapshot(self, start_ts: int) -> None:
        with self._tso_lock:
            n = self._active_snapshots.get(start_ts, 0) - 1
            if n <= 0:
                self._active_snapshots.pop(start_ts, None)
            else:
                self._active_snapshots[start_ts] = n

    def run_gc(self, safepoint: int | None = None) -> int:
        """MVCC GC pass (ref: gc_worker.go): the effective safepoint is
        clamped strictly below every active transaction — both registered
        snapshots (read-only txns included) and lock holders — so no
        in-flight snapshot loses its read view and no write-conflict check
        loses the tombstone it compares against. Default safepoint = the
        current TSO (keep only the latest committed version per key).
        Returns versions removed."""
        sp = safepoint if safepoint is not None else self.next_ts()
        with self._tso_lock:
            for ts in self._active_snapshots:
                sp = min(sp, ts - 1)
        with self.txn._mu:
            for l in self.txn.locks.values():
                sp = min(sp, l.start_ts - 1)
        self.gc_safepoint = max(getattr(self, "gc_safepoint", -1), sp)
        return self.kv.gc(sp)

    def _bump_write_ver(self):
        # the bump rides the caches' own lock (vet finding: the unlocked
        # `+= 1` could lose an increment between two racing writers, and
        # the TOCTOU guard in _may_file compares EXACT versions).
        # every key of the three caches embeds the old write version, so
        # entries can never serve stale data — the clear just frees dead
        # weight: host bytes, HBM, and places in the LRU windows
        from ..util import failpoint, tracing

        failpoint.eval("store/before-bump-write-ver")  # the commit's rows are in the kv, the version is the old one
        with tracing.span("store.cache_drop") as sp:
            with self._cop_lock:
                self._write_ver += 1
                entries = len(self._chunk_cache) + len(self._cop_cache) + len(self._batch_cache)
                device_bytes = self._batch_cache.used
                dead = self._drop_version_caches()
            del dead  # outside the lock: the batches' HBM is returned here
            if sp is not None:
                sp.attrs.update(entries=entries, device_bytes=device_bytes)

    def _snapshot_write_ver(self) -> int:
        """Locked read of the store write version — the pre-read snapshot
        every cache key embeds."""
        with self._cop_lock:
            return self._write_ver

    def _may_file(self, write_ver: int, start_ts: int) -> bool:  # requires: _cop_lock
        """The filing half of the snapshot rule (_SnapshotCache), one place
        for the three caches: may what a snapshot at `start_ts` read be
        filed under `write_ver`, the caller's snapshot of _write_ver taken
        BEFORE it read the region? Not if a write landed since (version
        moved, or a half-applied commit already raised kv.max_version) —
        a pre-write read could be filed under the post-write key and serve
        stale rows. And not for a snapshot that predates some committed
        version: it would cache a view NEWER snapshots must not inherit
        (MVCC: same write_ver, different visibility) — only the all-seeing
        snapshot caches. Asked and acted on in one hold of _cop_lock
        (max_committed takes kv.lock INSIDE it; that order is one-way —
        nothing holding kv.lock ever takes _cop_lock)."""
        return write_ver == self._write_ver and start_ts >= self.kv.max_committed()

    def _record_write_flow(self, key: bytes, value: bytes | None, prev_live: bool,
                           ts: int, placement: tuple | None = None):
        """Per-key write flow into the PD heartbeat snapshot (ref: TiKV's
        flow observer feeding pdpb.RegionHeartbeat bytes/keys_written) +
        a replication proposal carrying the change entry: the write rides
        the region's raft-lite log, commits on quorum ack, advances
        follower safe_ts, and feeds any subscribed changefeed."""
        self.pd.flow.record_write(key, 0 if value is None else len(value),
                                  prev_live=prev_live, delete=value is None)
        if placement is None:
            placement = self.cluster.locate_placement(key)
        rid, leader, peers = placement
        self.replication.propose(rid, ts, placement=(leader, peers),
                                 entries=[(key, value)])

    def record_applied_writes(self, items, ts: int | None = None):
        """Batch write flow for appliers that land many keys at once (2PC
        commit, bulk ingest, LOAD DATA): items of (key, value|None,
        prev_live). Called AFTER the kv critical section so the flow
        bookkeeping never extends the reader-blocking window. Each touched
        region gets ONE replication proposal at the batch's commit ts
        (a raft batch-proposal, not per-key entries) carrying exactly its
        own keys' changes — the CDC puller sees the log sharded the way
        the raft log is. `ts` defaults to the store commit watermark for
        legacy callers; batch appliers pass their actual commit_ts so
        events never wear a concurrent commit's timestamp."""
        self.pd.flow.record_writes(
            [(k, 0 if v is None else len(v), prev, v is None) for k, v, prev in items]
        )
        if ts is None:
            ts = self.kv.max_committed()
        values = {k: v for k, v, _prev in items}
        for rid, keys in self.cluster.group_keys_by_region(list(values)).items():
            self.replication.propose(rid, ts,
                                     entries=[(k, values[k]) for k in keys])

    def record_applied_writes_grouped(self, lanes):
        """Group-commit write flow (ISSUE 19): lanes of (applied items,
        commit_ts) from ONE coalesced window, ascending commit ts. One
        flow-stats batch for the whole window, then ONE replication
        proposal per touched region carrying every lane's entries at its
        own commit ts (ReplicaManager.propose_group) — N sessions cost
        one quorum round per region instead of N."""
        from ..util import metrics

        flow_items = []
        per_region: dict[int, list] = {}
        pairs = 0
        for applied, ts in lanes:
            flow_items.extend(
                (k, 0 if v is None else len(v), prev, v is None)
                for k, v, prev in applied
            )
            values = {k: v for k, v, _prev in applied}
            for rid, keys in self.cluster.group_keys_by_region(list(values)).items():
                per_region.setdefault(rid, []).append(
                    (ts, [(k, values[k]) for k in keys])
                )
                pairs += 1
        self.pd.flow.record_writes(flow_items)
        for rid, groups in per_region.items():
            self.replication.propose_group(rid, groups)
        if pairs > len(per_region):
            metrics.COALESCE_GROUP_PROPOSALS_SAVED.inc(pairs - len(per_region))

    def _check_write_quorum(self, keys) -> None:
        """The pre-apply write gate (ROADMAP PR-8 follow-on): every
        region a write touches must hold quorum, else the whole write is
        refused with a typed QuorumLostError (MySQL 9005 at the session
        boundary) BEFORE anything turns durable on the shared KV. One
        cluster-lock acquisition fetches every placement."""
        for rid, placement in self.cluster.placements_of_keys(keys).items():
            self.replication.check_write_quorum(rid, placement=placement)

    # -- write path (ref: table.AddRecord -> memdb -> prewrite/commit) ------
    def put_row(self, table_id: int, handle: int, col_ids: list[int], datums: list[Datum], ts: int):
        key = tablecodec.encode_row_key(table_id, handle)
        val = self._row_encoder.encode(col_ids, datums)
        placement = self.cluster.locate_placement(key)
        self.replication.check_write_quorum(placement[0], placement=placement[1:])
        with self.cdc.guard.writing():
            prev = self.kv.put(key, val, ts)
            self._record_write_flow(key, val, prev, ts, placement=placement)
        self._bump_write_ver()

    def delete_row(self, table_id: int, handle: int, ts: int):
        key = tablecodec.encode_row_key(table_id, handle)
        placement = self.cluster.locate_placement(key)
        self.replication.check_write_quorum(placement[0], placement=placement[1:])
        with self.cdc.guard.writing():
            prev = self.kv.put(key, None, ts)
            self._record_write_flow(key, None, prev, ts, placement=placement)
        self._bump_write_ver()

    def put_index(self, key: bytes, value: bytes, ts: int):
        placement = self.cluster.locate_placement(key)
        self.replication.check_write_quorum(placement[0], placement=placement[1:])
        with self.cdc.guard.writing():
            prev = self.kv.put(key, value, ts)
            self._record_write_flow(key, value, prev, ts, placement=placement)
        self._bump_write_ver()

    def propose_schema_change(self, meta, op: str, query: str) -> int:
        """One committed row-shape DDL -> one schema-change entry riding
        `ReplicaManager.propose` (ISSUE 20: DDL through the feed). The
        key is synthetic (`m_schema_<tid>_<ver>`, never in KV); the ts
        draws INSIDE the CDC WriteGuard so no resolved-ts candidate can
        prove quiescence past an undelivered schema change — exactly the
        row write paths' ordering guarantee. The journal records it
        first: a feed that misses the live delivery (paused, born later,
        puller-drop) re-injects from the journal on its next tick."""
        from ..cdc.schema import encode_schema_key, schema_payload
        import json as _json

        key = encode_schema_key(meta.table_id, meta.schema_version)
        value = _json.dumps(schema_payload(meta, op, query)).encode()
        with self.cdc.guard.writing():
            ts = self.next_ts()
            self.schema_journal.append(ts, meta.table_id, key, value)
            rid = self.cluster.locate_placement(
                tablecodec.table_prefix(meta.table_id))[0]
            self.replication.propose(rid, ts, entries=[(key, value)])
        return ts

    # -- scan/decode with caching -------------------------------------------
    # Byte budgets of the two decode caches (PERF.md section 3). Host: what
    # a store may keep decoded beside the MVCC data it was decoded from.
    # Device: one part in `_DECODE_DEVICE_PART` of the chip's `bytes_limit`,
    # so that a program's sort and gather temporaries over a resident batch
    # (a few times the batch) and the columnar replica fit beside the cache;
    # a backend that reports no limit (the CPU) keeps its "device" batches
    # in host memory and gets the host budget.
    _DECODE_HOST_BYTES = 1 << 30
    _DECODE_DEVICE_PART = 8

    def region_chunk(self, region: Region, ranges: list, dag: DAGRequest, start_ts: int) -> Chunk:
        """Rows of `region` ∩ `ranges` visible at `start_ts`, decoded to a
        columnar chunk: the resident one where `_region_read` finds it."""
        return self._region_read(region, ranges, dag, start_ts)[0]

    def _region_read(self, region: Region, ranges: list, dag: DAGRequest, start_ts: int, device: bool = False) -> tuple:
        """-> (chunk, its DeviceBatch or None, hit): a region's rows as the
        programs take them, from the decode caches where they hold them.

        Both caches are keyed by the region's data version (epoch and store
        write version: any write to the store invalidates — coarse, but
        correct; per-region versions later) and by what was read (table,
        ranges, and each column as the result cache's fingerprint names it:
        id, type, flag, flen, decimal, default — MODIFY COLUMN changes how
        stored bytes decode and writes nothing), never by the statement's
        timestamp, and both follow the result cache's snapshot rule
        (_SnapshotCache): a later statement finds what an earlier one
        decoded, a transaction whose snapshot predates a commit misses,
        decodes at its own timestamp and files nothing. `hit`: everything
        asked for was resident; nothing was scanned, decoded or uploaded.
        No program donates or writes into a batch it is handed, so one
        batch serves concurrent statements."""
        from ..util import metrics

        scan = dag.scan()
        what = self._read_key(region, ranges, scan.table_id, tuple(c.fingerprint() for c in scan.columns))
        batch = None
        with self._cop_lock:
            ver = self._write_ver  # the pre-read snapshot: in the keys, and gates the filing
            ch = self._chunk_cache.get((ver, what), start_ts)
            if ch is not None and device:
                batch = self._batch_cache.get((ver, what), start_ts)
        hit = ch is not None and (batch is not None or not device)
        (metrics.COP_DECODE_HITS if hit else metrics.COP_DECODE_MISSES).inc()
        if hit:
            return ch, batch, True
        decoded = uploaded = None
        try:
            if ch is None:
                ch = decoded = self._decode_region(region, ranges, scan, start_ts)
            if device:
                batch = uploaded = to_device_batch(ch, capacity=batch_rung(ch.num_rows()))
        finally:  # a chunk whose upload raised is the oracle fall-back's input: filed too
            self._file_decoded(ver, what, start_ts, decoded, uploaded)
        return ch, batch, False

    @staticmethod
    def _read_key(region: Region, ranges: list, table_id: int, columns: tuple) -> tuple:
        """What a region read names its rows by in the decode caches'
        keys, beside the store's write version; `columns` are the scan's,
        each by its fingerprint."""
        return (region.region_id, region.epoch, table_id, columns, tuple((r.start, r.end) for r in ranges))

    def _file_decoded(self, ver: int, what: tuple, start_ts: int, chunk: Chunk | None, batch: DeviceBatch | None) -> None:
        """File what a read at `start_ts` decoded and uploaded (either may
        be None) under the data version `ver` it started from, if the
        snapshot rule allows, and count what leaves for the budgets.
        `what` is a region read's key or a mesh launch's (`_stacked_lanes`):
        the device batches of both share one budget."""
        from ..util import metrics

        if batch is not None and self._device_budget is None:
            import jax

            limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
            self._device_budget = limit // self._DECODE_DEVICE_PART if limit else self._DECODE_HOST_BYTES
        gone = []  # let go of after the lock: that is where a batch's HBM is returned
        with self._cop_lock:
            if self._may_file(ver, start_ts):
                if chunk is not None:
                    gone += self._chunk_cache.put((ver, what), chunk, start_ts, chunk.nbytes(), self._DECODE_HOST_BYTES)
                if batch is not None:
                    gone += self._batch_cache.put((ver, what), batch, start_ts, batch.nbytes(), self._device_budget)
        if gone:
            metrics.COP_DECODE_EVICTIONS.inc(len(gone))

    def _decode_region(self, region: Region, ranges: list, scan, start_ts: int) -> Chunk:
        """Scan region ∩ ranges out of the MVCC store at the snapshot and
        decode the rows to columns: natively where the shape allows."""
        fts = [c.ft for c in scan.columns]
        fts_by_id = {c.col_id: c.ft for c in scan.columns}
        ch = None
        from ..exec.dag import IndexScan

        if not isinstance(scan, IndexScan):
            ch = self._native_region_chunk(region, ranges, scan, start_ts)
        if ch is None:
            rows = []
            for key, val in self._scan_region_kvs(region, ranges, start_ts):
                row = self._decode_row(key, val, scan, fts_by_id)
                if row is not None:
                    rows.append(row)
            ch = Chunk.from_rows(fts, rows)
        return ch

    def _scan_region_kvs(self, region: Region, ranges: list, start_ts: int):
        """(key, value) pairs of region ∩ ranges at the snapshot — the one
        range-clamping loop both decode paths consume."""
        for rng in ranges:
            start = max(rng.start, region.start_key)
            end = min(rng.end, region.end_key)
            if start >= end:
                continue
            yield from self.kv.scan(start, end, start_ts)

    def _native_region_chunk(self, region: Region, ranges: list, scan, start_ts: int) -> Chunk | None:
        """C++ scan decode (tidb_tpu/native): rowcodec values -> columns in
        one call. None on any unsupported shape or decode error — the
        caller runs the row-at-a-time Python decoder instead."""
        from .. import native

        if not native.available():
            return None
        if any(c.default is not None for c in scan.columns):
            return None  # origin-default fill is python-side only
        values: list[bytes] = []
        handles: list[int] = []
        for rng in ranges:
            start = max(rng.start, region.start_key)
            end = min(rng.end, region.end_key)
            if start >= end:
                continue
            for key, val in self.kv.scan(start, end, start_ts):
                try:
                    _, handle = tablecodec.decode_row_key(key)
                except ValueError:
                    continue
                values.append(val)
                handles.append(handle)
        cols = native.decode_rows_columnar(values, handles, scan.columns)
        if cols is None:
            return None
        from ..util import metrics

        metrics.NATIVE_DECODES.inc()
        return Chunk(cols)

    def _decode_row(self, key: bytes, val: bytes, scan, fts_by_id: dict):
        from ..exec.dag import IndexScan

        if isinstance(scan, IndexScan):
            return self._decode_index_entry(key, scan)
        try:
            _, handle = tablecodec.decode_row_key(key)
        except ValueError:
            return None
        dmap = decode_row_to_datum_map(val, fts_by_id)
        row = []
        for c in scan.columns:
            if c.col_id == -1:  # handle column (_tidb_rowid)
                row.append(Datum.i64(handle))
                continue
            row.append(fill_origin_default(val, c.col_id, c.default, dmap[c.col_id]))
        return row

    def _decode_index_entry(self, key: bytes, scan):
        """Index key `t{tid}_i{iid}{vals...}{handle}` -> one row of the
        IndexScan schema (index cols then handle; ref: indexScanExec
        mpp_exec.go:255 decoding index entries back to datums)."""
        from ..codec.datum_codec import decode_datums

        prefix_len = 1 + 8 + 2 + 8  # 't' + tid + '_i' + iid
        if len(key) <= prefix_len:
            return None
        fts = [c.ft for c in scan.columns]
        try:
            datums = decode_datums(key[prefix_len:], fts)
        except (ValueError, IndexError):
            return None
        if len(datums) != len(scan.columns):
            return None
        return datums

    def _paged_region_chunk(self, region: Region, ranges: list, dag: DAGRequest, start_ts: int, limit: int):
        """Scan at most `limit` rows of region ∩ ranges; returns
        (chunk, resume_ranges | None). The resume cursor is the first
        unscanned key, exactly the reference's lastRange contract
        (ref: cop_handler.go:210-224)."""
        scan = dag.scan()
        fts = [c.ft for c in scan.columns]
        fts_by_id = {c.col_id: c.ft for c in scan.columns}
        rows: list = []
        for ri, rng in enumerate(ranges):
            start = max(rng.start, region.start_key)
            end = min(rng.end, region.end_key)
            if start >= end:
                continue
            for key, val in self.kv.scan(start, end, start_ts):
                if len(rows) >= limit:
                    resume = [KeyRange(key, rng.end)] + list(ranges[ri + 1 :])
                    return Chunk.from_rows(fts, rows), resume
                row = self._decode_row(key, val, scan, fts_by_id)
                if row is not None:
                    rows.append(row)
        return Chunk.from_rows(fts, rows), None

    _AUX_CACHE_MAX = 16

    def _chunk_token(self, chunk: Chunk) -> int:
        """Monotonic identity for a chunk object. id() is reused after GC —
        a dead build side's cache entry could alias a brand-new chunk at
        the same address; a token handed out once per object never can."""
        tok = getattr(chunk, "_device_token", None)
        if tok is None:
            with self._aux_lock:
                tok = getattr(chunk, "_device_token", None)
                if tok is None:
                    tok = next(self._chunk_tokens)
                    chunk._device_token = tok
        return tok

    def _aux_batches(self, dag: DAGRequest, chunks: list, mesh_devices: int = 0) -> list:
        """The join build sides of `dag` as its program takes them
        (`_aux_batch`). A build side the statement materialised (a subquery's
        rows, a table id below 0: `sql/subquery.py`) is handed over at a
        sticky capacity rung of the plan's shape
        (`ProgramCache.input_capacity`), so that a set whose size moves with
        the inner statement's literals, from none to thousands of rows,
        calls one program; a table's build side keeps its own size."""
        from ..exec.dag import collect_scans

        builds = collect_scans(dag.executors)[1:]
        out = []
        for i, chunk in enumerate(chunks):
            cap = None
            if i < len(builds) and builds[i].table_id < 0:
                cap = self.programs.input_capacity(dag, ("aux", i), chunk.num_rows())
            out.append(self._aux_batch(chunk, mesh_devices, capacity=cap))
        return out

    def _aux_batch(self, chunk: Chunk, mesh_devices: int = 0, capacity: int | None = None) -> DeviceBatch:
        """Broadcast build-side chunk -> DeviceBatch, uploaded once per
        chunk object (all region tasks of a join share the operand). For a
        mesh launch over `mesh_devices` the batch is an entry of its own,
        replicated over the mesh's devices as the program's `in_specs`
        read it, so the call replicates nothing. `capacity` (rows the batch
        holds) defaults to the chunk's rows rounded up to a power of two.

        Bounded LRU keyed by the chunk token (never-reused identity); the
        entry pins the chunk so the device batch and its source live and
        die together."""
        from ..util import tracing

        key = self._chunk_token(chunk)
        if capacity is not None:
            key = (key, mesh_devices, capacity)
        sharding = None
        if mesh_devices:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.mesh import region_mesh

            if capacity is None:
                key = (key, mesh_devices)
            sharding = NamedSharding(region_mesh(mesh_devices), PartitionSpec())
        with tracing.span("cop.aux_batch", rows=chunk.num_rows()) as sp:
            batch, hit = self._kept_aux(key, chunk, lambda: to_device_batch(
                chunk, capacity=capacity or _pow2(max(chunk.num_rows(), 1)), sharding=sharding))
            if sp is not None:
                sp.set("hit", hit)
            return batch

    def _kept_aux(self, key, chunk: Chunk, make) -> tuple:
        """(the batch kept under `key`, True), its LRU position refreshed;
        else (`make()`, False), uploaded, counted and kept, pinning `chunk`,
        the oldest entry past `_AUX_CACHE_MAX` dropped."""
        from ..util import metrics

        with self._aux_lock:
            cached = self._aux_batch_cache.pop(key, None)
            if cached is not None:
                self._aux_batch_cache[key] = cached  # refresh LRU position
                return cached[1], True
        metrics.COP_AUX_UPLOADS.inc()
        batch = make()
        with self._aux_lock:
            self._aux_batch_cache[key] = (chunk, batch)
            while len(self._aux_batch_cache) > self._AUX_CACHE_MAX:
                self._aux_batch_cache.pop(next(iter(self._aux_batch_cache)))
        return batch, False

    def build_side(self, chunks: list) -> Chunk | None:
        """A build table's region chunks as one chunk: `SelectResult.merged()`
        with the concatenation of several kept under the parts' tokens, so
        that a table in several regions, answered from the result cache as
        the same chunk objects for every statement of a data version, is
        handed to `_aux_batch` as one object a data version too, as a lone
        chunk is. Bounded like the aux batches; the entry pins its parts
        (a token names a live object)."""
        if len(chunks) < 2:
            return chunks[0] if chunks else None
        key = tuple(self._chunk_token(c) for c in chunks)
        with self._aux_lock:
            kept = self._build_side_cache.pop(key, None)
            if kept is not None:
                self._build_side_cache[key] = kept  # refresh LRU position
                return kept[1]
        merged = Chunk.concat(chunks)
        with self._aux_lock:
            # two statements at once: the first one's object is what both hand on
            kept = self._build_side_cache.setdefault(key, (tuple(chunks), merged))
            while len(self._build_side_cache) > self._AUX_CACHE_MAX:
                self._build_side_cache.pop(next(iter(self._build_side_cache)))
        return kept[1]

    # -- coprocessor result cache (ref: copr/coprocessor_cache.go) ----------
    _COP_CACHE_MAX = 128

    def _cop_cache_key(self, req: CopRequest, write_ver: int):
        return (
            req.region_id,
            req.region_epoch,
            write_ver,
            (req.whole_dag or req.dag).fingerprint(),
            tuple((r.start, r.end) for r in req.ranges),
            req.small_groups,
        )

    def _cop_cacheable(self, req: CopRequest) -> bool:
        # paging responses carry per-page cursors; aux chunks (join build
        # sides) are statement-local operands with no data version to key on
        return req.paging_size is None and not req.aux_chunks

    def _cop_cache_get(self, req: CopRequest) -> CopResponse | None:
        """Serve a whole region response from the result cache when the
        region's data version — (epoch, store write version) — and the DAG
        fingerprint match, under the snapshot rule the decode caches share
        (_SnapshotCache). A hit still records read flow — the region
        logically served the rows, and hiding cached traffic from the PD
        would blind the hot-region scheduler to exactly the hottest (most
        re-read) regions."""
        if not self._cop_cacheable(req):
            return None
        with self._cop_lock:
            ent = self._cop_cache.get(self._cop_cache_key(req, self._write_ver), req.start_ts)
        if ent is None:
            return None
        resp, flow = ent
        from ..topsql import record_cop_cache_hit
        from ..util import metrics

        metrics.COP_CACHE_HITS.inc()
        record_cop_cache_hit()  # zero device time by construction: no launch ran
        self.pd.flow.record_read(req.region_id, flow[0], flow[1])
        summaries = [replace(s, cache_hit=True, time_compile_ns=0) for s in resp.exec_summaries]
        return CopResponse(chunk=resp.chunk, exec_summaries=summaries, root_fused=resp.root_fused)

    def _cop_cache_put(self, req: CopRequest, resp: CopResponse,
                       flow: tuple = (0, 0), write_ver: int | None = None) -> None:
        """flow = (decoded bytes, rows) of the region read — replayed into
        the PD heartbeat on every hit so flow stats see cached traffic.

        write_ver is the caller's snapshot of _write_ver taken BEFORE it
        read the region: `_may_file` refuses the insert if a write landed
        since."""
        if (
            not self._cop_cacheable(req)
            or resp.chunk is None
            or resp.region_error is not None
            or resp.other_error is not None
            or resp.last_range is not None
        ):
            return
        with self._cop_lock:
            ver = self._write_ver if write_ver is None else write_ver
            if self._may_file(ver, req.start_ts):
                self._cop_cache.put(self._cop_cache_key(req, ver), (resp, flow), req.start_ts, 1, self._COP_CACHE_MAX)

    def _count_replica_read(self, req: CopRequest) -> None:
        """tidb_tpu_replica_read_total{target=} — one count per routed
        request (req.peer_store >= 0), marker-deduped because a batch lane
        can be re-served by the single-request path (singleton groups,
        overflow fall-outs) after the batch already admitted it. Also
        feeds the closest-replica router's per-store read load."""
        if req.peer_store < 0 or getattr(req, "_replica_counted", False):
            return
        req._replica_counted = True
        from ..util import metrics

        target = ("follower"
                  if req.peer_store != self.cluster.leader_of(req.region_id)
                  else "leader")
        metrics.REPLICA_READS.labels(target).inc()
        self.replication.note_read(req.peer_store)

    def _region_fault(self, region_id: int, peer_store: int = -1,
                      replica_read: bool = False, start_ts: int = 0):
        """The typed fault ladder for the peer a request was routed to
        (`peer_store`; -1 = whoever leads at serve time): the set_down
        switch and the three per-store-armable failpoints
        (`store/unreachable`, `store/not-leader`, `store/server-busy`) —
        each returns a typed RegionError the dispatch client classifies
        onto its own backoff budget — then the replication checks: a
        non-leader peer answers NotLeader WITH the current leader as the
        hint unless the request is a replica read, and a replica read is
        gated on the peer's applied watermark (`safe_ts >= start_ts`,
        else DataIsNotReady — ref: TiKV replica read's resolved-ts
        check). None = this peer serves."""
        from ..util import failpoint
        from .errors import DataIsNotReady, NotLeader, ServerIsBusy, StoreUnavailable

        leader = self.cluster.leader_of(region_id)
        sid = peer_store if peer_store >= 0 else leader
        if self.store_down(sid):
            return StoreUnavailable.make(sid)
        if _fault_matches(failpoint.eval("store/unreachable"), sid):
            return StoreUnavailable.make(sid)
        if _fault_matches(failpoint.eval("store/not-leader"), sid):
            # injected leadership wobble: the hint is whatever the cluster
            # currently believes — pointing at the armed store itself
            # means "election in flight", no usable hint
            return NotLeader.make(region_id, sid, leader)
        busy = failpoint.eval("store/server-busy")
        if _fault_matches(busy, sid):
            ms = busy.get("backoff_ms", 0) if isinstance(busy, dict) else 0
            return ServerIsBusy.make(sid, ms)
        if sid != leader:
            if not replica_read:
                return NotLeader.make(region_id, sid, leader)
            safe = self.replication.safe_ts(region_id, sid)
            if safe < start_ts:
                return DataIsNotReady.make(region_id, sid, safe)
        return None

    # -- the serialized endpoint (the sidecar seam) -------------------------
    def coprocessor_bytes(self, req_bytes: bytes) -> bytes:
        """Serve one cop request from wire bytes to wire bytes — the
        process-boundary shape of the coprocessor endpoint (ref:
        unistore/rpc.go:260 CmdCop dispatch over serialized protos). A
        sidecar server loop is exactly `recv -> coprocessor_bytes -> send`."""
        from ..codec.wire import decode_cop_request, encode_cop_response

        try:
            req = decode_cop_request(req_bytes)
        except Exception as exc:  # malformed bytes must not kill the server
            return encode_cop_response(CopResponse(other_error=f"bad request: {exc}"))
        return encode_cop_response(self.coprocessor(req))

    # -- the coprocessor endpoint -------------------------------------------
    def coprocessor(self, req: CopRequest, group_capacity: int = DEFAULT_GROUP_CAPACITY) -> CopResponse:
        from ..util import failpoint, metrics

        metrics.COP_REQUESTS.inc()
        t_start = time.monotonic()
        resp = self._coprocessor(req, group_capacity)
        metrics.COP_DURATION.observe(time.monotonic() - t_start)
        if resp.region_error is not None or resp.other_error is not None:
            metrics.COP_ERRORS.inc()
        return resp

    def _coprocessor(self, req: CopRequest, group_capacity: int) -> CopResponse:
        from ..exec.dag import executor_walk
        from ..util import failpoint, metrics, tracing

        if failpoint.eval("cop-region-error"):
            # fault injection at the RPC seam (ref: unistore/rpc.go:265-271)
            return CopResponse(region_error="injected epoch_not_match")
        if failpoint.eval("cop-other-error"):
            return CopResponse(other_error="injected coprocessor error")
        region = self.cluster.region_by_id(req.region_id)
        if region is None:
            return CopResponse(region_error=f"region {req.region_id} not found")
        err = self._region_fault(req.region_id, req.peer_store,
                                 req.replica_read, req.start_ts)
        if err is not None:
            return CopResponse(region_error=str(err))
        if req.region_epoch != region.epoch:
            return CopResponse(region_error=f"epoch_not_match: have {region.epoch}, got {req.region_epoch}")
        self._count_replica_read(req)
        if req.paging_size is not None:
            req.whole_dag = None  # a page is not the statement's one state
        # a lone cop task is the statement's whole input: its unsplit DAG
        # runs over the region's batch as ONE program (what the columnar
        # route does with a whole DAG), and the root has nothing to merge
        fused = req.whole_dag is not None
        dag = req.whole_dag if fused else req.dag
        cached = self._cop_cache_get(req)
        if cached is not None:
            return cached
        ver = self._snapshot_write_ver()  # pre-read snapshot: gates the cache insert
        t0 = time.monotonic_ns()
        last_range = None
        page = rc = None
        in_bytes, in_rows = 0, 0
        info = {"cache_hit": False, "compile_ns": 0}
        try:
            with tracing.span("cop.decode", region_id=req.region_id) as dsp:
                sent = upload_transfers()
                if req.paging_size is not None:
                    from ..exec.dag import Aggregation as _Agg, Limit as _Limit, Sort as _Sort, TopN as _TopN

                    if req.paging_size <= 0:
                        return CopResponse(other_error=f"invalid paging_size {req.paging_size}")
                    if any(isinstance(e, (_Agg, _TopN, _Limit, _Sort)) for e in executor_walk(dag.executors)):
                        # per-page agg/top-k/limit results are not mergeable by
                        # concatenation — row-local DAGs only (scan/sel/proj/join)
                        return CopResponse(other_error="paging requires a row-local DAG (no aggregation/TopN/Limit)")
                    page, last_range = self._paged_region_chunk(
                        region, req.ranges, dag, req.start_ts, req.paging_size
                    )
                    in_bytes, in_rows = page.nbytes(), page.num_rows()
                    batch, hit = to_device_batch(page, capacity=batch_rung(page.num_rows())), False
                else:
                    rc, batch, hit = self._region_read(region, req.ranges, dag, req.start_ts, device=True)
                    in_bytes, in_rows = rc.nbytes(), rc.num_rows()
                # read flow into the PD heartbeat (ref: TiKV flow observer
                # -> pdpb.RegionHeartbeat bytes/keys_read); a resident
                # region logically served its rows too
                self.pd.flow.record_read(region.region_id, in_bytes, in_rows)
                if dsp is not None:
                    dsp.set("bytes_to_device", in_bytes)
                    dsp.set("rows", in_rows)
                    dsp.set("hit", hit)
                    dsp.set("transfers", upload_transfers() - sent)
            batches = [batch] + self._aux_batches(dag, req.aux_chunks)
            with tracing.span("cop.execute", region_id=req.region_id, root_fused=fused) as xsp:
                chunk, ex_rows, info = drive_program_info(self.programs, dag, batches, group_capacity,
                                                          small_groups=req.small_groups)
                if xsp is not None:
                    xsp.set("rows", chunk.num_rows())
                    xsp.set("cache_hit", info["cache_hit"])
        except (RuntimeError, TypeError) as exc:
            soft = isinstance(exc, (OverflowRetryError, NotImplementedError))
            if not soft and failpoint.eval("cop-debug-raise"):
                raise  # surface kernel bugs with a stack when armed
            if fused:
                # the whole DAG did not fit one program (its capacity
                # retries ran out, or its root half holds what the device
                # cannot express): serve the pushdown half as if the root's
                # had never come, and the root merges with its own ladder,
                # spill and oracle
                req.whole_dag = None
                return self._coprocessor(req, group_capacity)
            if not soft:
                return CopResponse(other_error=str(exc))
            # degenerate fan-out OR an op the device cannot express (JSON,
            # regexp, host-only funcs reaching a pushed executor): fall back
            # to the row-at-a-time oracle (SURVEY §7 / exec/builder.py)
            from ..util import metrics as _m

            _m.COP_FALLBACKS.inc()
            try:
                with tracing.span("cop.oracle_fallback", region_id=req.region_id):
                    region_chunk = page if page is not None else rc
                    if region_chunk is None:  # the read itself raised (an upload the device cannot take)
                        region_chunk = self.region_chunk(region, req.ranges, req.dag, req.start_ts)
                    rows = run_dag_reference(req.dag, [region_chunk] + list(req.aux_chunks))
                    chunk = Chunk.from_rows(req.dag.output_fts(), rows)
                # fallback summaries: aligned with the device path's
                # per-executor walk (build pipelines included); counts are
                # the final row count
                ex_rows = [chunk.num_rows()] * len(executor_walk(req.dag.executors))
                info = {"cache_hit": False, "compile_ns": 0}
            except (RuntimeError, TypeError, NotImplementedError, ValueError) as exc:
                if failpoint.eval("cop-debug-raise"):
                    raise  # loud-failure gate (VERDICT r2 weak #10)
                return CopResponse(other_error=f"oracle fallback failed: {exc}")
        elapsed = time.monotonic_ns() - t0
        from ..topsql import record_device

        record_device(compile_ns=info["compile_ns"], bytes_to_device=in_bytes)
        # per-executor produced-row counts are real (measured inside the
        # fused program); the time is the whole fused program's — XLA fuses
        # the pipeline into one kernel, so per-operator time does not exist
        # (ref: cop_handler.go:518-531 fills per-executor summaries).
        # compile/cache attribution is likewise per-program: every summary
        # of the task carries it; bytes attribute to the data movers (the
        # scan's decoded region bytes in, the final executor's result out).
        walk = executor_walk(dag.executors)
        out_bytes = chunk.nbytes()
        summaries = [
            ExecSummary(
                time_processed_ns=elapsed, num_produced_rows=r,
                time_compile_ns=info["compile_ns"], cache_hit=info["cache_hit"],
                num_bytes=in_bytes if i == 0 else (out_bytes if i == len(ex_rows) - 1 else 0),
            )
            for i, r in enumerate(ex_rows)
        ]
        _apply_radix_attribution(summaries, walk, info)
        for ex, r in zip(walk, ex_rows):
            metrics.COP_EXECUTOR_ROWS.labels(type(ex).__name__.lower()).inc(r)
        resp = CopResponse(chunk=chunk, exec_summaries=summaries, last_range=last_range, root_fused=fused)
        self._cop_cache_put(req, resp, flow=(in_bytes, in_rows), write_ver=ver)
        return resp

    # -- the batched coprocessor endpoint -----------------------------------
    def batch_coprocessor(self, reqs: list[CopRequest], group_capacity: int = DEFAULT_GROUP_CAPACITY) -> list[CopResponse]:
        """Serve a store's worth of region tasks from ONE vmapped XLA
        launch per (DAG fingerprint, snapshot) group (ref:
        copr/batch_coprocessor.go — all regions of a TiFlash store travel
        in one request). Every region's rows decode as usual, pad to the
        group's shared power-of-two capacity, stack along a leading region
        axis, and execute as a single vmapped program; per-region partial
        results slice back out, so the root-side merge is unchanged.

        Per-request validation happens UP FRONT: a stale epoch, missing
        region or cache hit answers immediately and falls out of the batch
        — the rest of the batch still executes. Paging requests and armed
        cop failpoints route through the single-request path (resume
        cursors and injection sites live there). Responses come back in
        request order.

        The root's half (the requests' `whole_dag`) is run only by a group
        that IS the call: every request of it in one group, none answered
        from the cache, faulted or paged out of it. Such a group's mesh
        launch goes on through the root's half behind its merge; a lane on
        its own is one region of several, so the field is taken off every
        request of a call of several before anything is served or looked up
        (a lane's cop result is filed under its pushdown half), and the root
        merges what the mesh launch did not."""
        from ..util import failpoint, metrics

        responses: list = [None] * len(reqs)
        groups: dict = {}
        whole = reqs[0].whole_dag if reqs else None
        if len(reqs) > 1:
            for req in reqs:
                req.whole_dag = None
        for i, req in enumerate(reqs):
            if (
                req.paging_size is not None
                or failpoint.is_armed("cop-region-error")
                or failpoint.is_armed("cop-other-error")
            ):
                responses[i] = self.coprocessor(req, group_capacity)
                continue
            region = self.cluster.region_by_id(req.region_id)
            if region is None:
                metrics.COP_REQUESTS.inc()
                metrics.COP_ERRORS.inc()
                responses[i] = CopResponse(region_error=f"region {req.region_id} not found")
                continue
            err = self._region_fault(req.region_id, req.peer_store,
                                     req.replica_read, req.start_ts)
            if err is not None:
                # typed store faults fall out of the batch exactly like a
                # stale epoch: the lane answers immediately, the rest of
                # the batch stands (region errors survive the batch frame
                # as strings, same as the single-request seam)
                metrics.COP_REQUESTS.inc()
                metrics.COP_ERRORS.inc()
                responses[i] = CopResponse(region_error=str(err))
                continue
            if req.region_epoch != region.epoch:
                metrics.COP_REQUESTS.inc()
                metrics.COP_ERRORS.inc()
                responses[i] = CopResponse(
                    region_error=f"epoch_not_match: have {region.epoch}, got {req.region_epoch}"
                )
                continue
            self._count_replica_read(req)
            cached = self._cop_cache_get(req)
            if cached is not None:
                metrics.COP_REQUESTS.inc()
                responses[i] = cached
                continue
            key = (
                req.dag.fingerprint(),
                req.start_ts,
                req.small_groups,
                bool(req.mesh),
                tuple(self._chunk_token(c) for c in req.aux_chunks),
            )
            groups.setdefault(key, []).append((i, req, region))
        for entries in groups.values():
            if len(entries) == 1:  # nothing to amortize: the plain path
                i, req, _region = entries[0]
                responses[i] = self.coprocessor(req, group_capacity)
                continue
            if entries[0][1].mesh and self._run_cop_mesh(
                    entries, responses, group_capacity, whole if len(entries) == len(reqs) else None):
                continue  # merged on device; else degrade to the vmap tier
            self._run_cop_batch(entries, responses, group_capacity)
        return responses

    # data-size floor for the mesh tier on ACTUAL decoded rows (the
    # client's estimate only gated the attempt): below it the vmapped
    # batch tier serves — a shard_map launch is not worth its compile
    # for a handful of rows. Env-tunable for benches.
    MESH_MIN_GROUP_ROWS = int(os.environ.get("TIDB_TPU_MESH_MIN_ROWS", "0"))

    def _run_cop_mesh(self, entries, responses, group_capacity: int, whole: DAGRequest | None = None) -> bool:
        """ONE shard_map launch for a same-DAG group of region tasks (the
        dispatch planner's MESH tier): decode every lane, stack to the
        group's max pow2 capacity, pad the region axis onto the device
        mesh, and merge the per-region partial states ON DEVICE — psum
        over the region axis for additive aggregate states, pmin/pmax for
        extremes, a merge-mode re-group for GROUP BY tables, a re-top-k
        for TopN. The group's first lane answers with the ONE merged
        chunk; the rest answer empty with the same mesh_merged marker, so
        the root-side merge consumes a single state per store.

        `whole` (the statement's unsplit DAG, handed over only where this
        group is the whole request) makes the same launch finish the
        statement: behind the merge the program runs the root's half over
        the one merged state, the carrier lane's chunk holds the
        statement's rows, every lane answers `root_fused`, and the root
        merges nothing. A degrade or an overflow (the flag covers the
        root's half, which has no ladder of its own) leaves the root's half
        to the root, as if it had never come.

        Returns True when every lane was answered; False degrades the
        whole group to the vmapped batch tier (ineligible DAG, too few
        rows, overflow, or any trace/launch failure) — which owns the
        per-lane capacity ladder and the oracle fallback. With
        `cop-debug-raise` armed a decode, trace or launch failure raises
        instead, as on the single-request path: the size, skew and
        overflow declines stay counted declines."""
        import jax

        from ..distsql.planner import mesh_merge_kind
        from ..exec.dag import executor_walk
        from ..exec.executor import drive_mesh_program_info
        from ..util import failpoint, metrics, tracing

        req0 = entries[0][1]
        dag = req0.dag
        kind = mesh_merge_kind(dag)
        if kind is None:
            return False
        t0 = time.monotonic_ns()
        D = min(len(jax.devices()), len(entries))
        ver = self._snapshot_write_ver()  # pre-read snapshot: in the stacked batch's key, and gates its filing
        try:
            with tracing.span("cop.mesh_decode", regions=len(entries)) as dsp:
                chunks = [
                    self.region_chunk(region, req.ranges, dag, req.start_ts)
                    for (_i, req, region) in entries
                ]
                if dsp is not None:
                    dsp.set("bytes_to_device", sum(ch.nbytes() for ch in chunks))
                aux_batches = self._aux_batches(dag, req0.aux_chunks, mesh_devices=D)
        except Exception:  # noqa: BLE001 — degrade, never lose the group
            if failpoint.eval("cop-debug-raise"):
                raise
            return False
        floor = max(self.MESH_MIN_GROUP_ROWS, req0.mesh_min_rows)
        if sum(ch.num_rows() for ch in chunks) < floor:
            # data-size tier rule: small groups ride vmap (counted like
            # every other mesh decline so dashboards can tell "declined"
            # from "never attempted")
            metrics.MESH_COP_FALLBACKS.inc()
            return False
        caps = [_pow2(max(ch.num_rows(), 1)) for ch in chunks]
        cap = max(caps)
        # skew guard (#review): every lane pads to the group MAX capacity,
        # so one post-split giant among small regions would inflate the
        # stacked footprint toward lanes*max — the exact hazard the vmap
        # tier's pow2 BUCKETING exists for. When padding would waste >4x
        # the honest per-lane footprint, degrade to the bucketed tier.
        if cap * len(caps) > 4 * sum(caps):
            metrics.MESH_COP_FALLBACKS.inc()
            return False
        try:
            with tracing.span("cop.mesh_execute", regions=len(entries),
                              devices=D, kind=kind, root_fused=whole is not None) as xsp:
                stacked = self._stacked_lanes(ver, dag.scan(), req0.start_ts,
                                              [(region, req.ranges) for _i, req, region in entries], chunks, cap, D)
                merged, lane_counts, info = drive_mesh_program_info(
                    self.programs, whole or dag, stacked, aux_batches, group_capacity,
                    kind, D, small_groups=req0.small_groups, root=whole is not None,
                )
                if xsp is not None:
                    xsp.set("cache_hit", info["cache_hit"])
        except Exception:  # noqa: BLE001 — degrade, never lose the group
            if failpoint.eval("cop-debug-raise"):
                raise
            metrics.MESH_COP_FALLBACKS.inc()
            return False
        if merged is None:
            # global overflow flag: the vmapped tier's PER-LANE ladder
            # isolates the overflowing region instead
            metrics.MESH_COP_FALLBACKS.inc()
            return False
        elapsed = time.monotonic_ns() - t0
        from ..topsql import record_device, split_by_rows

        # one launch served every lane: attribution splits by each lane's
        # decoded rows (not an equal share — a 10k-row lane did the work a
        # 10-row lane did not), and the shares sum EXACTLY to the launch
        # total in EXPLAIN ANALYZE (Top SQL's device time is the state
        # clock's exec.wait, not this)
        shares = split_by_rows(elapsed, [ch.num_rows() for ch in chunks])
        record_device(compile_ns=info["compile_ns"],
                      bytes_to_device=sum(ch.nbytes() for ch in chunks))
        walk = executor_walk(dag.executors)
        out_fts = merged.field_types()
        metrics.MESH_COP_BATCHES.inc()
        for k, (i, req, region) in enumerate(entries):
            metrics.MESH_COP_LANES.inc()
            # the first lane carries the one merged state; the rest answer
            # empty — concatenation at root sees exactly one row block per
            # store, the "no per-region host merge" contract
            out_chunk = merged if k == 0 else Chunk.empty(out_fts)
            summaries = self._lane_attribution(
                region, chunks[k], out_chunk.nbytes() if k == 0 else 0,
                lane_counts[k], shares[k],
                compile_ns=info["compile_ns"] if k == 0 else 0,
                cache_hit=info["cache_hit"] if k == 0 else True, walk=walk,
                # the carrier lane owns the merged result — it carries the
                # group-total join_radix attribution too
                radix_info=info if k == 0 else None,
            )
            # NOT cop-cached: the merged state covers the whole group, not
            # one region's data version — a later request with a different
            # lane set must not inherit it
            responses[i] = CopResponse(
                chunk=out_chunk, exec_summaries=summaries, batched=1,
                mesh_merged=len(entries), root_fused=whole is not None,
            )
        return True

    def _stacked_lanes(self, ver: int, scan, start_ts: int, lanes: list | None, chunks: list,
                       cap: int, D: int) -> DeviceBatch:
        """The lanes of a mesh launch as the program reads them: one batch,
        every leaf's region axis sharded over the mesh's `D` devices. Kept
        with the regions' own device batches (`_batch_cache`: one budget,
        one snapshot rule, dropped with them) under the data version `ver`
        and what the lanes read (`lanes`: each chunk's region and ranges,
        `scan` its columns), so a later statement over the same lanes finds
        it resident on the devices, whichever cross-chip tier asks; else
        stacked on the host and put there once, each device receiving its
        own lanes. `lanes` None: the chunks name no region read, and the
        batch is stacked and put for this launch alone."""
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import REGION_AXIS, region_mesh
        from ..util import metrics, tracing

        R_pad = -(-len(chunks) // D) * D  # empty lanes pad the region axis
        what = None
        if lanes is not None:
            columns = tuple(c.fingerprint() for c in scan.columns)
            what = ("mesh.stack", cap, R_pad, D,
                    tuple(self._read_key(region, ranges, scan.table_id, columns) for region, ranges in lanes))
        with tracing.span("mesh.stack", lanes=R_pad, devices=D,
                          rows=sum(ch.num_rows() for ch in chunks),
                          bytes=sum(ch.nbytes() for ch in chunks)) as sp:
            stacked = None
            if what is not None:
                with self._cop_lock:
                    stacked = self._batch_cache.get((ver, what), start_ts)
            hit = stacked is not None
            (metrics.MESH_STACK_HITS if hit else metrics.MESH_STACK_MISSES).inc()
            if sp is not None:
                sp.set("hit", hit)
            if not hit:
                fts = chunks[0].field_types()
                padded = list(chunks) + [Chunk.empty(fts) for _ in range(R_pad - len(chunks))]
                stacked = to_stacked_device_batch(
                    padded, cap, NamedSharding(region_mesh(D), PartitionSpec(REGION_AXIS)))
                if what is not None:
                    self._file_decoded(ver, what, start_ts, None, stacked)
        return stacked

    def exchange_lanes(self, ver: int, scan, start_ts: int, lanes: list | None, chunks: list,
                       D: int) -> DeviceBatch:
        """The exchange tier's probe lanes (`mpp/dispatch.py`): the
        regions' chunks of a statement's scan stacked as the per-request
        mesh tier stacks them (a power-of-two capacity, the region axis
        padded onto the `D` devices and sharded over them) and kept by the
        same key, so both tiers find one resident batch where they read the
        same columns and ranges (`_stacked_lanes`)."""
        cap = max(_pow2(max(ch.num_rows(), 1)) for ch in chunks)
        return self._stacked_lanes(ver, scan, start_ts, lanes, chunks, cap, D)

    def exchange_build(self, chunk: Chunk, n: int) -> DeviceBatch:
        """A shuffle join's build table for the exchange tier over `n`
        devices: sliced into `n` lanes (a slice plays a region shard) and
        stacked, sharded over the devices. Kept, like `_aux_batch`, by the
        chunk object (a build side answered from the result cache is the
        same object a data version: `build_side`), so a later statement
        finds it on the devices; the entry pins the chunk."""
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import REGION_AXIS, region_mesh

        rows = chunk.num_rows()
        step = max(-(-rows // n), 1)

        def stack() -> DeviceBatch:
            slices = [chunk.slice(i * step, min((i + 1) * step, rows)) for i in range(n) if i * step < rows]
            slices += [Chunk.empty(chunk.field_types()) for _ in range(n - len(slices))]
            return to_stacked_device_batch(slices, max(1, max(c.num_rows() for c in slices)),
                                           NamedSharding(region_mesh(n), PartitionSpec(REGION_AXIS)))

        return self._kept_aux((self._chunk_token(chunk), "exchange", n), chunk, stack)[0]

    def _lane_attribution(self, region, in_chunk, out_bytes: int, counts,
                          share: int, compile_ns: int, cache_hit: bool,
                          walk, radix_info=None) -> list:
        """Shared per-lane attribution for the vmapped-bucket and mesh
        launch loops: PD read flow, cop metrics, and the ExecSummary list
        (the fused program's time shared across the lane's executors;
        bytes attribute to the data movers — scan in, final executor
        out). Keeping ONE copy means EXPLAIN ANALYZE / flow accounting
        changes cannot drift between the two batched tiers."""
        from ..util import metrics

        self.pd.flow.record_read(region.region_id, in_chunk.nbytes(),
                                 in_chunk.num_rows())
        metrics.COP_REQUESTS.inc()
        metrics.COP_DURATION.observe(share / 1e9)
        in_b = in_chunk.nbytes()
        summaries = [
            ExecSummary(
                time_processed_ns=share, num_produced_rows=r,
                time_compile_ns=compile_ns, cache_hit=cache_hit,
                num_bytes=in_b if j == 0 else (out_bytes if j == len(counts) - 1 else 0),
            )
            for j, r in enumerate(counts)
        ]
        if radix_info:
            _apply_radix_attribution(summaries, walk, radix_info)
        for ex, r in zip(walk, counts):
            metrics.COP_EXECUTOR_ROWS.labels(type(ex).__name__.lower()).inc(r)
        return summaries

    def _run_cop_batch(self, entries, responses, group_capacity: int) -> None:
        """Decode a same-DAG group of region tasks, bucket by shared pow2
        capacity, and launch one vmapped program per bucket — the
        documented (store, DAG-fingerprint, capacity) launch unit. Without
        the bucketing, one skewed region would pad EVERY lane to its size
        and a 16-region batch could cost ~16x the per-region footprint.
        Lanes whose overflow flag fired — and a whole bucket on any
        batched-trace failure — degrade to the single-request path, which
        owns the capacity ladder and the oracle fallback."""
        from ..util import tracing

        req0 = entries[0][1]
        dag = req0.dag
        ver = self._snapshot_write_ver()  # pre-read snapshot: gates the cache inserts
        try:
            with tracing.span("cop.batch_decode", regions=len(entries)) as dsp:
                chunks = [
                    self.region_chunk(region, req.ranges, dag, req.start_ts)
                    for (_i, req, region) in entries
                ]
                if dsp is not None:
                    dsp.set("bytes_to_device", sum(ch.nbytes() for ch in chunks))
                aux_batches = self._aux_batches(req0.dag, req0.aux_chunks)
        except Exception:  # noqa: BLE001 — degrade, never lose the batch
            for i, req, _region in entries:
                responses[i] = self.coprocessor(req, group_capacity)
            return
        buckets: dict[int, list] = {}
        for k, ch in enumerate(chunks):
            buckets.setdefault(_pow2(max(ch.num_rows(), 1)), []).append(k)
        batch_id = 0
        for cap, idxs in buckets.items():
            if len(idxs) == 1:  # nothing to amortize at this capacity
                i, req, _region = entries[idxs[0]]
                responses[i] = self.coprocessor(req, group_capacity)
                continue
            batch_id += 1
            self._launch_cop_bucket(
                [entries[k] for k in idxs], [chunks[k] for k in idxs], cap,
                aux_batches, responses, group_capacity, ver, batch_id,
            )

    def _launch_cop_bucket(self, entries, chunks, cap: int, aux_batches,
                           responses, group_capacity: int, write_ver: int,
                           batch_id: int) -> None:
        """ONE vmapped launch for a capacity bucket of decoded regions."""
        from ..exec.dag import executor_walk
        from ..util import metrics, tracing

        req0 = entries[0][1]
        dag = req0.dag
        # per-bucket clock: a later bucket's lanes must not be billed for
        # earlier buckets' launches (decode is cached and near-free here)
        t0 = time.monotonic_ns()
        try:
            with tracing.span("cop.batch_execute", regions=len(entries),
                              capacity=cap) as xsp:
                # pow2 lane axis: vmap_batch rides the ProgramCache key,
                # so an unpadded lane count would compile a fresh program
                # per batch size — coalesced windows (ISSUE 19) arrive at
                # every size. Empty pad lanes cost rows=0 decode, same as
                # the mesh tier's region-axis padding.
                B_pad = _pow2(len(chunks))
                lanes = list(chunks)
                if B_pad > len(lanes):
                    fts = chunks[0].field_types()
                    lanes += [Chunk.empty(fts) for _ in range(B_pad - len(lanes))]
                stacked = to_stacked_device_batch(lanes, cap)
                per_region, info = drive_batched_program_info(
                    self.programs, dag, stacked, aux_batches, group_capacity,
                    small_groups=req0.small_groups,
                )
                if xsp is not None:
                    xsp.set("cache_hit", info["cache_hit"])
        except Exception:  # noqa: BLE001 — degrade, never lose the bucket
            # oracle-only ops, CI non-ASCII routing, vmap-ineligible shapes:
            # the single path reproduces the error handling contract
            # (other_error / oracle fallback / cop-debug-raise) per region
            for i, req, _region in entries:
                responses[i] = self.coprocessor(req, group_capacity)
            return
        elapsed = time.monotonic_ns() - t0
        from ..topsql import record_device, split_by_rows

        # per-lane attribution by decoded rows (exact: shares sum to the
        # launch total); overflow fall-out lanes keep their share here —
        # the launch still spent it — and bill their retry separately
        shares = split_by_rows(elapsed, [ch.num_rows() for ch in chunks])
        record_device(compile_ns=info["compile_ns"],
                      bytes_to_device=sum(ch.nbytes() for ch in chunks))
        walk = executor_walk(dag.executors)
        metrics.BATCH_COP_BATCHES.inc()
        served = 0
        for lane, ((i, req, region), ch, res) in enumerate(zip(entries, chunks, per_region)):
            if res is None:
                # this lane's group/join/topn capacity overflowed: only it
                # rides the single-request retry ladder
                responses[i] = self.coprocessor(req, group_capacity)
                continue
            chunk, ex_rows = res
            metrics.BATCH_COP_REGIONS.inc()
            # read flow ONLY for lanes the batch actually served — fall-out
            # lanes (and whole-bucket degrades) record theirs inside the
            # single path, so the PD never sees a region's read twice.
            # compile time belongs to the ONE shared program: the first lane
            # carries it, the rest are cache hits by construction
            lane_info = info
            if info.get("radix"):
                # each lane's summaries carry its OWN escape count (the
                # batch total would multiply across EXPLAIN's summary sum)
                by_lane = info["radix"].get("escapes_by_lane") or []
                lane_info = {"radix": dict(
                    info["radix"],
                    escapes=by_lane[lane] if lane < len(by_lane) else 0,
                )}
            summaries = self._lane_attribution(
                region, ch, chunk.nbytes(), ex_rows, shares[lane],
                compile_ns=info["compile_ns"] if served == 0 else 0,
                cache_hit=info["cache_hit"] if served == 0 else True, walk=walk,
                radix_info=lane_info,
            )
            served += 1
            resp = CopResponse(chunk=chunk, exec_summaries=summaries, batched=batch_id)
            self._cop_cache_put(req, resp, flow=(ch.nbytes(), ch.num_rows()), write_ver=write_ver)
            responses[i] = resp
        if served > 1:
            metrics.BATCH_COP_LAUNCHES_SAVED.inc(served - 1)

    def batch_coprocessor_bytes(self, req_bytes: bytes) -> bytes:
        """The sidecar seam of the batched endpoint: one frame of N cop
        requests in, one frame of N responses out (ref: the BatchCommands /
        BatchCop stream framing over serialized protos)."""
        from ..codec.wire import decode_batch_cop_request, encode_batch_cop_response

        try:
            reqs = decode_batch_cop_request(req_bytes)
        except Exception as exc:  # malformed bytes must not kill the server
            return encode_batch_cop_response([CopResponse(other_error=f"bad batch request: {exc}")])
        return encode_batch_cop_response(self.batch_coprocessor(reqs))
