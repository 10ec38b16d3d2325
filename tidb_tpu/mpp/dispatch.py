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
from .fragment import chunks_exchange_safe, fragment_plan, mesh_eligible, split_join_dag

# (program key, n devices, base group capacity) -> last successful
# (gc, scale) ladder rung; bounded FIFO, see execute_exchange_plan
_LADDER_HINTS: dict[tuple, tuple[int, int]] = {}


def _chunks_nbytes(chunks) -> int:
    total = 0
    for c in chunks:
        if c is not None:
            total += int(c.nbytes())
    return total


def execute_exchange_plan(dag, chunks, aux_chunks, kind, devs,
                          group_capacity: int = 1024, programs=None) -> Chunk | None:
    """Launch the exchange program over already-scanned chunks. Region
    chunks play the task lanes; build tables are sliced across devices so
    each slice plays a region shard. Overflow (too many groups / join
    fan-out / hash collision) retries with 4x capacity — the capacity
    also salts the hash, mirroring drive_program's contract — reusing the
    scanned chunks, not rescanning. The programs live in `programs` (the
    store's ProgramCache). Returns the projected result Chunk, or None for
    a fallback to the per-region path (a decline; a failure raises)."""
    from ..parallel.grouped import run_sharded_grouped_agg
    from ..parallel.mesh import region_mesh, stack_region_batches
    from ..util import metrics, tracing

    agg = dag.executors[-1]
    out_fts = agg.output_fts()
    if not chunks:
        # zero rows scanned: grouped aggregation of nothing is no groups
        return Chunk.empty([out_fts[i] for i in dag.output_offsets])
    if not chunks_exchange_safe(chunks):
        return None  # wide strings cannot ride the exchange byte-exactly

    n = len(devs)
    n_total = ((len(chunks) + n - 1) // n) * n
    mesh = region_mesh(n)
    stacked_builds = None
    # host-side stacking and upload of the task lanes, made anew for every
    # statement: the probe's region chunks, then each build table sliced
    # across the devices so that a slice plays a region shard
    with tracing.span("mesh.stack", lanes=n_total, devices=n,
                      rows=sum(c.num_rows() for c in chunks), bytes=_chunks_nbytes(chunks)):
        try:
            stacked = stack_region_batches(chunks, n_total=n_total)
        except NotImplementedError:
            return None  # e.g. non-ASCII CI data: the per-region path's
            # oracle fallback owns it (chunk/device.py guard)
        if kind == "join":
            n_stages = len(split_join_dag(dag)[2])
            if aux_chunks is None or len(aux_chunks) < n_stages:
                return None
            stacked_builds = []
            for build in aux_chunks[:n_stages]:
                if not chunks_exchange_safe([build]):
                    return None
                if build.num_rows() == 0:
                    bslices = [build]
                else:
                    step = (build.num_rows() + n - 1) // n
                    bslices = [
                        build.slice(i * step, min((i + 1) * step, build.num_rows()))
                        for i in range(n)
                        if i * step < build.num_rows()
                    ]
                try:
                    stacked_builds.append(stack_region_batches(bslices, n_total=n))
                except NotImplementedError:
                    return None  # non-ASCII CI build data -> per-region path

    # the ladder's start rung is remembered per plan SHAPE (the key of the
    # program itself): a skewed key distribution that overflowed rung 1 last
    # time will overflow it again — a repeated statement, whatever its
    # literals, starts at the rung that last succeeded, so the steady state
    # is ONE cached program, not a re-walk of the failed rungs
    hint_key = (dag.program_key(), n, group_capacity)
    gc, scale = _LADDER_HINTS.get(hint_key, (group_capacity, 1))
    with tracing.span("mpp.exchange", kind=kind) as sp:
        for retries in range(3):
            # a failure in here (an op the device compiler refuses that
            # slipped past the static gate, a failed launch) is the
            # caller's to count or to raise: try_mpp_select
            if kind == "join":
                from .exchange_op import run_exchange_join_agg

                chunk, overflow = run_exchange_join_agg(
                    dag, stacked, stacked_builds, mesh, group_capacity=gc, scale=scale,
                    programs=programs)
            else:
                chunk, overflow = run_sharded_grouped_agg(dag, stacked, mesh, group_capacity=gc,
                                                          programs=programs)
            if sp is not None:
                sp.set("rung", [gc, scale])
                sp.set("retries", retries)
            if not overflow:
                if len(_LADDER_HINTS) >= 256:
                    _LADDER_HINTS.pop(next(iter(_LADDER_HINTS)))
                _LADDER_HINTS[hint_key] = (gc, scale)
                metrics.MESH_SELECTS.inc()
                cols = [chunk.columns[i] for i in dag.output_offsets]
                return Chunk(cols)
            # one overflow flag covers groups, exchange buckets, and join
            # fan-out. Exchange/fan-out skew (scale) is far more common than
            # group-count overflow in chain shapes, and gc inflates the group
            # tables of EVERY device — so the middle rung grows scale alone,
            # and only the last rung grows both
            if scale >= 4:
                gc *= 4
            scale *= 4
    return None  # caller falls back to the per-region path


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
        if chunks is None:
            # row-store scan pushdown (paging/retry, typed region errors
            # and epoch fall-out preserved — a mid-query split raises the
            # same typed shape the per-region path does)
            from ..distsql.dispatch import KVRequest, select

            scan = dag.executors[0]
            scan_dag = DAGRequest((scan,), output_offsets=tuple(range(len(scan.columns))))
            with tracing.span("mpp.scan", table=scan.table_id):
                res = select(store, KVRequest(scan_dag, ranges, start_ts))
            chunks = [c for c in res.chunks if c is not None and c.num_rows() > 0]
        if failpoint.eval("mpp/exchange-stall"):
            # an exchange never delivered mid-run: abandon the MPP run
            metrics.MPP_FALLBACKS.inc()
            return None
        try:
            out = execute_exchange_plan(dag, chunks, aux_chunks, kind, devs,
                                        group_capacity=group_capacity, programs=store.programs)
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
