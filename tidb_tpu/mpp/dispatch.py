"""MPP dispatch — the coordination layer above the fragment planner and
the exchange operator (ref: pkg/executor/mpp_gather.go MPPGather +
store/copr/mpp.go DispatchMPPTask; unistore/cophandler/mpp.go handles the
task side).

The reference's coordinator cuts the plan into fragments, serializes each
fragment into a DispatchMPPTaskRequest per store, and gathers the root
fragment's PassThrough stream. Here the task topology IS the device mesh:
every fragment runs n_tasks SPMD tasks inside ONE shard_map program
(`exchange_op.run_exchange_join_agg` / `grouped.run_sharded_grouped_agg`),
so "dispatch" means (1) prove the fragment topology (`fragment_plan`) and
round-trip it through the wire codec — the executed plan is the DECODED
one, the same seam a real coordinator ships across the network — then
(2) source the probe-side scan, preferring the columnar replica's
device-resident stable chunks when the replica covers the snapshot
(`columnar_would_serve` + the data_not_ready readiness gate), falling back
to the row-store scan pushdown otherwise, and (3) launch the exchange
program with the overflow capacity ladder.

Failure discipline mirrors `columnar/route.py`: every decline is a COUNTED
fallback (`MPP_FALLBACKS`) and the caller (`distsql.execute_root`, which
asks `choose_statement_tier` first) goes on with its own tiers as if
routing never happened — degrade, never fail; the row store still owns
the authoritative answer. With `cop-debug-raise` armed a failure of the
exchange program fails the statement instead, as on the single-request
path and the columnar route: a run that is there to measure this tier
must not read another's numbers. Size, skew and eligibility declines stay
counted declines either way. Typed region errors and epoch fall-out surface
from the row-store scan path itself (`distsql.dispatch.select`), so a
mid-query region split aborts the MPP attempt with the same typed shape
the per-region path raises.

Failpoints:
  mpp/dispatch-lost   a task dispatch is lost before launch — counted
                      fallback to `execute_root`.
  mpp/exchange-stall  an exchange never delivers mid-run — the
                      coordinator abandons the run (counted fallback).
"""

from __future__ import annotations

from ..chunk import Chunk
from ..exec.dag import DAGRequest
from .fragment import chunks_exchange_safe, exchange_columns, fragment_plan, mesh_eligible, split_join_dag

# (program key, n devices, base group capacity) -> last successful
# (gc, scale) ladder rung; bounded FIFO, see execute_exchange_plan
_LADDER_HINTS: dict[tuple, tuple[int, int]] = {}


def _chunks_nbytes(chunks) -> int:
    total = 0
    for c in chunks:
        if c is not None:
            total += int(c.nbytes())
    return total


def execute_exchange_plan(store, dag, chunks, lanes, aux_chunks, kind, devs,
                          group_capacity: int = 1024) -> Chunk | None:
    """Launch the exchange program over already-scanned chunks. Region
    chunks play the task lanes; build tables are sliced across devices so
    each slice plays a region shard. The group tables start at the rung
    that last held this plan shape's groups (`_LADDER_HINTS`); an overflow
    (too many groups / join fan-out / hash collision) retries on the rung
    that the program's need hint names, else one geometric step up
    (exec/ladder.py `overflow_step`; the capacity also salts the hash,
    mirroring drive_program's contract), reusing the scanned chunks, not
    rescanning. The programs live in the store's ProgramCache.

    The store keeps what is stacked: the probe lanes by data version and
    what they read (`lanes`, a `(write version, scan, start_ts, [(region,
    ranges)] | None)`: `TPUStore.exchange_lanes`, shared with the
    per-request mesh tier) and each build table by its chunk object
    (`TPUStore.exchange_build`).

    A tail behind the aggregate (`fragment.split_tail`: HAVING,
    projection) runs in the program where `tail_in_program` allows, else
    over the program's groups at the root (`run_dag_on_chunks`), inside
    the span `mpp.tail`. Returns the projected result Chunk, or None for a
    fallback to the per-region path (a decline; a failure raises)."""
    from ..exec.ladder import overflow_step
    from ..parallel.grouped import run_sharded_grouped_agg
    from ..parallel.mesh import region_mesh
    from ..util import metrics, tracing
    from .fragment import split_tail, tail_in_program

    head, tail = split_tail(dag)
    in_program = not tail or tail_in_program(tail)
    prog_dag = dag if in_program else head
    if not chunks:
        # zero rows scanned: grouped aggregation of nothing is no groups
        return Chunk.empty(dag.output_fts())
    probe_cols, build_cols = exchange_columns(head)
    if not chunks_exchange_safe(chunks, probe_cols):
        return None  # wide strings cannot ride the exchange byte-exactly

    n = len(devs)
    mesh = region_mesh(n)
    stacked_builds = None
    try:
        # the probe's region chunks as lanes, then each build table sliced
        # across the devices so that a slice plays a region shard
        ver, scan, start_ts, read = lanes
        stacked = store.exchange_lanes(ver, scan, start_ts, read, chunks, n)
        if kind == "join":
            n_stages = len(split_join_dag(head)[2])
            if aux_chunks is None or len(aux_chunks) < n_stages:
                return None
            if not all(chunks_exchange_safe([b], cols) for b, cols in zip(aux_chunks, build_cols)):
                return None
            stacked_builds = [store.exchange_build(build, n) for build in aux_chunks[:n_stages]]
    except NotImplementedError:
        return None  # e.g. non-ASCII CI data: the per-region path's
        # oracle fallback owns it (chunk/device.py guard)

    # the ladder's start rung is remembered per plan SHAPE (the key of the
    # program itself): a repeated statement, whatever its literals, starts
    # at the rung that last held its groups — the steady state is ONE
    # cached program, not a re-walk of the failed rungs
    hint_key = (prog_dag.program_key(), n, group_capacity)
    gc, scale = _LADDER_HINTS.get(hint_key, (group_capacity, 1))
    stats: dict = {}
    with tracing.span("mpp.exchange", kind=kind) as sp:
        for retries in range(3):
            # a failure in here (an op the device compiler refuses that
            # slipped past the static gate, a failed launch) is the
            # caller's to count or to raise: try_mpp_select
            if kind == "join":
                from .exchange_op import run_exchange_join_agg

                chunk, overflow = run_exchange_join_agg(
                    prog_dag, stacked, stacked_builds, mesh, group_capacity=gc, scale=scale,
                    programs=store.programs, stats=stats)
            else:
                chunk, overflow = run_sharded_grouped_agg(prog_dag, stacked, mesh, group_capacity=gc,
                                                          programs=store.programs, stats=stats)
            if sp is not None:
                sp.set("rung", [gc, scale])
                sp.set("retries", retries)
            if not overflow:
                if len(_LADDER_HINTS) >= 256:
                    _LADDER_HINTS.pop(next(iter(_LADDER_HINTS)))
                _LADDER_HINTS[hint_key] = (gc, scale)
                metrics.MESH_SELECTS.inc()
                break
            # one overflow flag covers groups, exchange buckets, and join
            # fan-out. A need hint past the rung is a pure group-count miss:
            # jump to the rung that holds it. Otherwise exchange/fan-out skew
            # (scale) is far more common than group-count overflow in chain
            # shapes, and gc inflates the group tables of EVERY device — so
            # the middle rung grows scale alone, and only the last grows both
            if stats.get("need", 0) > gc:
                gc = overflow_step(gc, 0, True, False, stats["need"], 0)[0]
            elif kind == "join" and scale < 4:
                scale *= 4
            else:
                gc, scale = overflow_step(gc, 0, True, False, 0, 0)[0], scale * 4
        else:
            return None  # caller falls back to the per-region path
    if not tail:
        return Chunk([chunk.columns[i] for i in dag.output_offsets])
    with tracing.span("mpp.tail", executors=[type(e).__name__ for e in tail], in_program=in_program,
                      rows_in=stats.get("groups", chunk.num_rows())) as tsp:
        if in_program:
            metrics.MPP_TAIL_STATEMENTS.inc()
            chunk = Chunk([chunk.columns[i] for i in dag.output_offsets])
        else:
            from ..distsql.root import tail_dag
            from ..exec.executor import run_dag_on_chunks

            chunk = run_dag_on_chunks(tail_dag(dag, head), [chunk], cache=store.programs)
        if tsp is not None:
            tsp.set("rows_out", chunk.num_rows())
    return chunk


def _replica_probe_chunks(store, dag, ranges, start_ts, n_lanes,
                          engines, backoff_weight, checker):
    """Source the probe scan from the columnar replica's stable chunks,
    sliced into n_lanes task shards. Returns a chunk list, or None when
    the replica does not cover the snapshot (the row-store scan pushdown
    is the fallback source — not a query failure)."""
    from ..columnar.replica import ColumnarNotReady, _schema_sig
    from ..columnar.route import _plan_intervals, _wait_ready, columnar_would_serve
    from ..util import metrics

    # the probe fragment's scan is the bare TableScan — the mpp eligibility
    # gate already proved the analytical shape, so would-serve is asked on
    # the FULL dag (Aggregation present) with the probe's ranges
    if not columnar_would_serve(store, dag, ranges, engines):
        return None
    rep = store.columnar
    plan = _plan_intervals(dag, ranges)
    if not plan:
        return None
    sig = _schema_sig(dag.scan().columns)
    tables = []
    for pid in plan:
        t = rep.table_for(pid)
        if t is None or t.schema_sig != sig:
            return None
    for pid in plan:
        tables.append(rep.table_for(pid))
    ts_eff = _wait_ready(store, tables, start_ts, backoff_weight, checker)
    if ts_eff is None:
        metrics.COLUMNAR_FALLBACKS.inc()
        return None
    try:
        scans = [t.scan(ts_eff, plan[pid]) for pid, t in zip(plan, tables)]
    except ColumnarNotReady:
        # a compaction advanced the floor between the gate and the scan
        metrics.COLUMNAR_FALLBACKS.inc()
        return None
    except Exception:  # noqa: BLE001 — degrade, never fail: the row
        # store still owns the authoritative answer
        metrics.COLUMNAR_FALLBACKS.inc()
        return None
    merged = scans[0][0] if len(scans) == 1 else Chunk.concat([c for c, _b in scans])
    rows = merged.num_rows()
    if rows == 0:
        return []
    step = (rows + n_lanes - 1) // n_lanes
    return [
        merged.slice(i * step, min((i + 1) * step, rows))
        for i in range(n_lanes)
        if i * step < rows
    ]


def _task_lanes(store, tasks, chunks) -> list | None:
    """[(region, ranges)] of the scan's chunks, one a task, where the scan
    answered each task with one chunk and no region moved under it (a
    split or a retry re-cuts the lanes): what names the stacked lanes in
    the store's cache. None: they are stacked for the one launch."""
    if len(chunks) != len(tasks):
        return None
    out = []
    for t in tasks:
        region = store.cluster.region_by_id(t.region_id)
        if region is None or region.epoch != t.epoch:
            return None
        out.append((region, t.ranges))
    return out


def try_mpp_select(
    store,
    dag: DAGRequest,
    ranges: list,
    start_ts: int,
    *,
    group_capacity: int = 1024,
    min_devices: int = 2,
    aux_chunks: list | None = None,
    engines: tuple = (),
    backoff_weight: int = 2,
    checker=None,
) -> Chunk | None:
    """Plan and run an eligible DAG as an MPP fragment graph; None = not
    taken (counted fallback — the caller dispatches to `execute_root` as
    if MPP routing never happened)."""
    kind = mesh_eligible(dag)
    if kind is None:
        return None
    if kind == "join" and not aux_chunks:
        return None
    import jax

    devs = jax.devices()
    if len(devs) < min_devices:
        return None
    fplan = fragment_plan(dag, n_tasks=len(devs))
    if fplan is None:
        return None
    from ..util import failpoint, metrics, tracing

    # the wire seam: a real coordinator ships each fragment inside a
    # DispatchMPPTaskRequest — round-trip the topology through the codec
    # so the EXECUTED plan is the decoded one, byte-exact
    from ..codec.wire import decode_fragment_plan, encode_fragment_plan

    fplan = decode_fragment_plan(encode_fragment_plan(fplan))
    if failpoint.eval("mpp/dispatch-lost"):
        # a task dispatch was lost before launch: abandon the MPP run
        metrics.MPP_FALLBACKS.inc()
        return None
    with tracing.span("mpp.dispatch", kind=kind, n_fragments=len(fplan.fragments),
                      n_tasks=fplan.n_tasks, n_ranges=len(ranges)) as sp:
        chunks = _replica_probe_chunks(
            store, dag, ranges, start_ts, len(devs), engines,
            backoff_weight, checker)
        replica_served = chunks is not None
        lanes = (None, None, start_ts, None)   # the replica's slices name no region read
        if chunks is None:
            # row-store scan pushdown (paging/retry, typed region errors
            # and epoch fall-out preserved — a mid-query split raises the
            # same typed shape the per-region path does)
            from ..distsql.dispatch import KVRequest, _build_tasks, select

            scan = dag.executors[0]
            scan_dag = DAGRequest((scan,), output_offsets=tuple(range(len(scan.columns))))
            ver = store._snapshot_write_ver()  # pre-read: in the stacked lanes' key, and gates their filing
            tasks = _build_tasks(store, ranges)
            with tracing.span("mpp.scan", table=scan.table_id):
                res = select(store, KVRequest(scan_dag, ranges, start_ts))
            chunks = [c for c in res.chunks if c is not None]
            lanes = (ver, scan, start_ts, _task_lanes(store, tasks, chunks))
            if lanes[3] is None:
                chunks = [c for c in chunks if c.num_rows() > 0]
        if failpoint.eval("mpp/exchange-stall"):
            # an exchange never delivered mid-run: abandon the MPP run
            metrics.MPP_FALLBACKS.inc()
            return None
        try:
            out = execute_exchange_plan(store, dag, chunks, lanes, aux_chunks, kind, devs,
                                        group_capacity=group_capacity)
        except Exception:  # noqa: BLE001 — degrade, never fail: the
            # per-region path still owns the answer (armed, the failure
            # is the answer: see the module docstring)
            if failpoint.eval("cop-debug-raise"):
                raise
            out = None
        if out is None:
            metrics.MPP_FALLBACKS.inc()
            return None
        metrics.MPP_SELECTS.inc()
        metrics.MPP_FRAGMENTS.inc(len(fplan.fragments))
        metrics.MPP_TASKS.inc(len(fplan.fragments) * fplan.n_tasks)
        metrics.MPP_EXCHANGED_BYTES.inc(
            _chunks_nbytes(chunks) + _chunks_nbytes(aux_chunks or []))
        if sp is not None:
            sp.set("rows", out.num_rows())
            sp.set("replica_served", replica_served)
        return out
