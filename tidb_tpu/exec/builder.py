"""DAG -> one fused XLA program.

The reference interprets a DAG as a pull-based iterator chain per batch
(ref: cophandler mppExecute pull loop, cop_handler.go:228). Here the whole
executor list traces into a *single* jitted function: scan columns in HBM ->
masked selection -> sort-based aggregation / topn / projection — XLA fuses
the lot, which is the TPU analog of the legacy fused closure executor
(ref: unistore/cophandler/closure_exec.go:165 buildClosureExecutor).

Programs cache by (program key, capacities, group/join capacity, tier) —
the XLA compile is the expensive part, amortized exactly like the
reference's coprocessor cache (ref: pkg/store/copr/coprocessor_cache.go).
The program key (`DAGRequest.program_key`, exec/dag.py) is the plan's
shape: what is traced is `dag.parameterized()`'s shape DAG, in which a
parameterisable literal is a `Param` seat and a scan names no table, and
the statement's values follow the batches as at most four operand arrays
(`dag.program_operands()`: int64, float64, string bytes and lengths).
One program per plan shape and capacity rung serves every literal and
every table of one DDL.

A program returns per-output-column (value, null[, raw bytes + lengths]),
plus row validity, row count and an overflow flag; on overflow (group/join
capacity exceeded) the host driver re-plans with a larger capacity or falls
back to the reference evaluator (SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..chunk.device import DeviceBatch
from ..expr.compile import CompVal, ExprCompiler, normalize_device_column
from ..ops import apply_selection, group_aggregate, hash_join, scalar_aggregate, topn
from ..ops import dense_pallas as _eager_dense_pallas  # noqa: F401
from ..ops import joinagg as _eager_joinagg  # noqa: F401
from ..ops import joinscan as _eager_joinscan  # noqa: F401

# ^ the packed-join modules are imported lazily on the hot path below, but
# MUST already be loaded before any jit trace starts: their module-level
# jnp constants (_PIN_HAY, I64_MAX, ...) would be staged as tracers if the
# first import happened inside the traced program, leaking into every
# later trace (jax UnexpectedTracerError, order-dependent).
from ..ops.aggregate import GatherState, finalize_agg
from ..types import FieldType
from . import launch
from .dag import Aggregation, DAGRequest, IndexScan, Join, Limit, Projection, Selection, Sort, TableScan, TopN, Window, collect_scans, current_schema_fts, operand_lanes
from .ladder import rung_for

DEFAULT_GROUP_CAPACITY = 4096


def _gather(cols: list[CompVal], idx) -> list[CompVal]:
    out = []
    for c in cols:
        raw = None
        if c.raw is not None:
            raw = (c.raw[0][idx], c.raw[1][idx])
        out.append(CompVal(c.value[idx], c.null[idx], c.ft, raw=raw))
    return out


@dataclass
class CompiledDAG:
    # what the launch boundary calls: `outputs.fn` is jitted, (DeviceBatch,
    # ..., operands) -> one byte buffer (exec/launch.py `HostOutputs`),
    # whose `read()` gives, as host arrays, (output_leaves, valid, n_rows,
    # overflow, ex_rows); the mesh variant (output_leaves, valid, ex_rows,
    # overflow, escapes)
    outputs: launch.HostOutputs
    # the same program as a traceable function with its outputs as device
    # arrays, `packed` in place of `output_leaves`: for a caller that
    # traces it into a program of its own (__graft_entry__.py's entry step)
    program: object
    out_fts: list[FieldType]
    capacities: tuple  # one per scan, canonical order (dag.collect_scans)
    group_capacity: int
    join_capacity: int
    # radix-join attribution, filled AT TRACE TIME (first execution): the
    # partition count / per-partition build capacity the plan chose; empty
    # when no Join rode the radix kernel.  The drivers read it after the
    # call to emit the `join_radix` span/summary (partitions, rung,
    # escapes) — see exec/executor.py.
    radix_info: dict = None  # type: ignore[assignment]
    # single-flight over the first call, where JAX traces and compiles
    gate: launch.FirstCallGate = field(default_factory=launch.FirstCallGate)

    @property
    def fn(self):
        return self.outputs.fn

    def host(self, *args):
        """Call the program and read its outputs, outside the launch
        boundary's spans and counters (tests, tools)."""
        return self.outputs.fn(*args).read()


class _TraceState:
    """Mutable trace-time accumulators shared across nested pipelines.

    Group and join overflow are SEPARATE flags so the retry driver grows
    only the capacity that actually overflowed (a 4x-per-retry growth on
    the wrong knob wastes HBM and compile time)."""

    def __init__(self, params: dict | None = None):
        self.group_overflow = jnp.bool_(False)
        self.join_overflow = jnp.bool_(False)
        self.topn_overflow = jnp.bool_(False)
        # capacity NEED hints riding next to the flags (exec/ladder.py):
        # the true group count / join fan-out when a kernel knows it, so
        # the retry driver re-dispatches the exact (precompiled) rung in
        # the SAME device fetch that read the overflow flag
        self.group_need = jnp.int64(0)
        self.join_need = jnp.int64(0)
        # radix-join attribution: escaped-row count (EXPLAIN/TRACE)
        self.radix_escapes = jnp.int64(0)
        self.radix_meta: dict = {}  # filled at trace time (partitions)
        self.radix_joins = True  # builder knob: False = monolithic only
        self.params = params or {}  # lane -> the traced operand array that `Param` seats read (expr/ir.py)
        self.ex_rows: list = []  # per-executor produced rows (EXPLAIN ANALYZE)

    def note_group(self, need):
        if need is not None:
            self.group_need = jnp.maximum(self.group_need, need.astype(jnp.int64))

    def note_join(self, need):
        if need is not None:
            self.join_need = jnp.maximum(self.join_need, need.astype(jnp.int64))

    def rows(self, arr_or_scalar):
        """Record a produced-row count. Accepts a precomputed scalar or a
        bool/int mask to sum."""
        v = arr_or_scalar
        if getattr(v, "ndim", 0) > 0:
            v = v.sum()
        self.ex_rows.append(v.astype(jnp.int64))


def _used_cols_after(rest, width: int, out_offsets):
    """Column indexes < width referenced by the remaining executors (or by
    the DAG outputs when the schema survives to the end) — the builder's
    column-pruning analog of the reference's columnPruner rule
    (pkg/planner/core/rule_column_pruning.go), applied at join output where
    every live column costs a ~16ns/row random gather on TPU.

    Schema-REPLACING executors (Projection/Aggregation) consume their
    inputs and cut the walk; schema-EXTENDING ones (Join, Window) preserve
    the prefix, so later references < width still mean these columns."""
    from ..expr.ir import ColumnRef, ScalarFunc

    used: set = set()

    def collect(e):
        if isinstance(e, ColumnRef):
            if e.index < width:
                used.add(e.index)
        elif isinstance(e, ScalarFunc):
            for a in e.args:
                collect(a)

    for ex in rest:
        if isinstance(ex, Selection):
            for c in ex.conditions:
                collect(c)
        elif isinstance(ex, (TopN, Sort)):
            for e, _ in ex.order_by:
                collect(e)
        elif isinstance(ex, Limit):
            pass
        elif isinstance(ex, Window):
            for e in ex.partition_by:
                collect(e)
            for e, _ in ex.order_by:
                collect(e)
            for w in ex.funcs:
                for a in w.args:
                    collect(a)
                if w.default is not None:
                    collect(w.default)
        elif isinstance(ex, Join):
            for e in ex.probe_keys:
                collect(e)
        elif isinstance(ex, Projection):
            for e in ex.exprs:
                collect(e)
            return used
        elif isinstance(ex, Aggregation):
            for e in ex.group_by:
                collect(e)
            for d in ex.aggs:
                for a in d.args:
                    collect(a)
            return used
    if out_offsets is None:
        return set(range(width))
    used.update(o for o in out_offsets if o < width)
    return used


def _gather_pruned(cols: list, idx, used: set, base: int) -> list:
    """Gather only the live columns; dead slots get an all-NULL zero column
    (schema positions preserved, no HBM traffic)."""
    n = idx.shape[0]
    out = []
    for j, c in enumerate(cols):
        if (base + j) in used:
            out.append(_gather([c], idx)[0])
        else:
            v = jnp.zeros((n,) + c.value.shape[1:], c.value.dtype)
            out.append(CompVal(v, jnp.ones(n, bool), c.ft))
    return out


def _run_pipeline(executors, batches, cursor, group_capacity, join_capacity, state: _TraceState, topn_full: bool = False, small_groups: int | None = None, unique_joins: bool = True, out_offsets=None, rows=None):
    """Trace one executor pipeline; recursion handles Join build sides.

    batches are consumed in canonical scan order (dag.collect_scans);
    `cursor` is the trace-time index of the next unconsumed batch.
    `rows` = (cols, valid, fts) stands in for the scan's batch where its
    output is already traced: the mesh program's merged state, which the
    root's half of the statement goes on from (`_build_mesh_fn`)."""
    scan = executors[0]
    assert isinstance(scan, (TableScan, IndexScan)), "pipeline must start with a scan"
    if rows is not None:
        cols, valid, fts = rows
    else:
        batch = batches[cursor[0]]
        cursor[0] += 1
        fts = [c.ft for c in scan.columns]
        cols = [normalize_device_column(c) for c in batch.cols]
        valid = batch.row_valid
        # per-executor produced-row counts, scan first (real numbers for the
        # exec summaries — ref: tipb.ExecutorExecutionSummary NumProducedRows)
        state.rows(batch.n_rows)

    ei = 1
    while ei < len(executors):
        ex = executors[ei]
        comp = ExprCompiler(fts, state.params)
        with jax.named_scope(stage_name(ex)):
            if isinstance(ex, Selection):
                conds = comp.run(list(ex.conditions), cols)
                valid = apply_selection(valid, conds)
            elif isinstance(ex, Projection):
                cols = comp.run(list(ex.exprs), cols)
                fts = [e.ft for e in ex.exprs]
            elif isinstance(ex, Limit):
                keep = jnp.cumsum(valid.astype(jnp.int32)) <= ex.limit
                valid = valid & keep
            elif isinstance(ex, TopN):
                order_vals = comp.run([e for e, _ in ex.order_by], cols)
                by = list(zip(order_vals, [d for _, d in ex.order_by]))
                idx, out_valid, t_ovf = topn(by, valid, ex.limit, full_sort=topn_full)
                state.topn_overflow = state.topn_overflow | t_ovf
                cols = _gather(cols, idx)
                valid = out_valid
            elif isinstance(ex, Sort):
                from ..ops.topn import sort_all

                order_vals = comp.run([e for e, _ in ex.order_by], cols)
                by = list(zip(order_vals, [d for _, d in ex.order_by]))
                idx, out_valid = sort_all(by, valid)
                cols = _gather(cols, idx)
                valid = out_valid
            elif isinstance(ex, Join):
                nxt = executors[ei + 1] if ei + 1 < len(executors) else None
                fused_ok = isinstance(nxt, Aggregation) and _joinagg_pattern(ex, nxt, len(fts), unique_joins)
                if fused_ok:
                    fused = _trace_packed_chain(
                        ex, nxt, comp, cols, valid, batches, cursor,
                        group_capacity, join_capacity, state, topn_full,
                        small_groups, unique_joins,
                    )
                    if fused is not None:
                        cols, valid, fts = fused
                        state.rows(valid)
                        ei += 2
                        continue
                bcols, bvalid, bfts = _run_pipeline(ex.build, batches, cursor, group_capacity, join_capacity, state, topn_full, small_groups, unique_joins)
                bcomp = ExprCompiler(bfts, state.params)
                bkeys = bcomp.run(list(ex.build_keys), bcols)
                pkeys = comp.run(list(ex.probe_keys), cols)
                _check_join_key_types(pkeys, bkeys)
                if fused_ok and _single_word(pkeys[0]) and _single_word(bkeys[0]):
                    fused = _trace_joinagg(
                        nxt, comp, cols, bkeys, pkeys, bvalid, valid,
                        group_capacity, state,
                    )
                    if fused is not None:
                        cols, valid, fts = fused
                        state.rows(valid)
                        ei += 2
                        continue
                res = _trace_radix_join(ex, bkeys, pkeys, bvalid, valid,
                                        join_capacity, state, unique_joins)
                if res is None:
                    res = hash_join(bkeys, pkeys, bvalid, valid, join_capacity, ex.join_type,
                                    build_unique=ex.build_unique and unique_joins)
                state.join_overflow = state.join_overflow | res.overflow
                state.note_join(res.need)
                if ex.join_type in ("semi", "anti"):
                    # probe schema preserved, rows filtered by match-existence
                    valid = res.out_valid
                else:
                    nb = bvalid.shape[0]
                    used = _used_cols_after(executors[ei + 1:], len(fts) + len(bfts), out_offsets)
                    if res.probe_identity:
                        p_g = cols  # unique-build layout: slot j == probe row j
                    else:
                        p_g = _gather_pruned(cols, res.probe_idx, used, 0)
                    b_g = _gather_pruned(bcols, jnp.clip(res.build_idx, 0, nb - 1), used, len(fts))
                    b_g = [CompVal(c.value, c.null | res.build_null, c.ft, raw=c.raw) for c in b_g]
                    cols = p_g + b_g
                    valid = res.out_valid
                    if ex.join_type == "left_outer":
                        bfts = [f.clone_nullable() for f in bfts]
                    fts = fts + bfts
            elif isinstance(ex, Window):
                from ..ops.window import window_cols

                part_vals = comp.run(list(ex.partition_by), cols) if ex.partition_by else []
                order_vals = comp.run([e for e, _ in ex.order_by], cols) if ex.order_by else []
                order_pairs = list(zip(order_vals, [d for _, d in ex.order_by]))
                funcs = []
                for w in ex.funcs:
                    argv = comp.run(list(w.args), cols) if w.args else []
                    if w.default is not None:
                        argv = argv + comp.run([w.default], cols)
                    funcs.append((w, argv))
                cols = cols + window_cols(part_vals, order_pairs, funcs, valid)
                fts = fts + [w.ft for w in ex.funcs]
            elif isinstance(ex, Aggregation):
                garg_exprs = []
                for a in ex.aggs:
                    garg_exprs.extend(a.args)
                gvals = comp.run(list(ex.group_by), cols) if ex.group_by else []
                avals = comp.run(list(garg_exprs), cols) if garg_exprs else []
                aggs = []
                k = 0
                for a in ex.aggs:
                    aggs.append((a, avals[k : k + len(a.args)]))
                    k += len(a.args)
                new_cols: list[CompVal] = []
                if ex.group_by:
                    res = group_aggregate(gvals, aggs, valid, group_capacity, merge=ex.merge, small_groups=small_groups, stream=ex.stream)
                    state.group_overflow = state.group_overflow | res.overflow
                    state.note_group(res.need)
                    for (a, av), st in zip(aggs, res.states):
                        new_cols.extend(_agg_result_cols(a, av, st, res.group_valid, ex.partial))
                    new_cols.extend(_gather(gvals, res.group_rep))
                    valid = res.group_valid
                else:
                    states, s_ovf = scalar_aggregate(aggs, valid, merge=ex.merge, salt=group_capacity)
                    state.group_overflow = state.group_overflow | s_ovf
                    ones = jnp.ones(1, bool)
                    for (a, av), st in zip(aggs, states):
                        new_cols.extend(_agg_result_cols(a, av, st, ones, ex.partial))
                    valid = ones
                cols = new_cols
                fts = ex.output_fts()
            else:
                raise TypeError(f"unsupported executor {ex}")
        state.rows(valid)
        ei += 1

    return cols, valid, fts


def _trace_radix_join(ex, bkeys, pkeys, bvalid, valid, join_capacity, state: _TraceState, unique_joins: bool):
    """Route an eligible Join through the radix-partitioned kernel
    (ops/radix_join.py); None = take the monolithic kernel.  Eligibility
    is decided SHAPE-ONLY — join shape, planner-proven unique build,
    single int-class key word, build/probe capacity ratio — before any
    value work, mirroring the packed-chain gate's contract."""
    from ..ops.radix_join import radix_hash_join, radix_plan

    if not (state.radix_joins and ex.build_unique and unique_joins):
        return None
    if ex.join_type not in ("inner", "left_outer", "semi", "anti"):
        return None
    if len(bkeys) != 1 or len(pkeys) != 1:
        return None
    if not (_single_word(bkeys[0]) and _single_word(pkeys[0])):
        return None
    if bkeys[0].eval_type == "real" or pkeys[0].eval_type == "real":
        return None  # float keys: NaN/-0.0 classes stay on the sort kernel
    plan = radix_plan(bvalid.shape[0], valid.shape[0], join_capacity)
    if plan is None:
        return None
    from ..ops.radix_join import probe_strategy

    mode = probe_strategy(*plan[:3])
    res, escapes = radix_hash_join(
        bkeys, pkeys, bvalid, valid, ex.join_type, join_capacity, plan,
        strategy=mode,
    )
    state.radix_escapes = state.radix_escapes + escapes
    # attribution reports what EXECUTED: the search strategy probes one
    # un-partitioned sorted build table (partitions=1, no escape hatch).
    # Program-level, first-radix-join-wins — the escape counter above
    # still totals across every radix join in the program
    state.radix_meta.setdefault("partitions", 1 if mode == "search" else plan[0])
    state.radix_meta.setdefault("part_cap", plan[1])
    state.radix_meta.setdefault("strategy", mode)
    return res


def _single_word(k: CompVal) -> bool:
    """True when the key normalizes to exactly one sort word (ops/keys.py
    layout: [null_flag, word]) — the joinagg kernel's key contract."""
    from ..ops.keys import sort_key_arrays

    return len(sort_key_arrays(k)) == 2


def _joinagg_pattern(ex, agg, n_probe_cols: int, unique_joins: bool) -> bool:
    """Join(unique build, inner) immediately under GROUP BY probe-key with
    probe-only aggregate arguments — the shape ops/joinagg.py fuses."""
    from ..expr.ir import ColumnRef, ScalarFunc
    from ..ops.joinagg import FUSABLE_AGGS

    if not (ex.join_type == "inner" and ex.build_unique and unique_joins):
        return False
    if len(ex.probe_keys) != 1 or len(ex.build_keys) != 1:
        return False
    if len(agg.group_by) != 1 or agg.group_by[0] != ex.probe_keys[0]:
        return False
    if agg.merge:
        return False

    def probe_only(e) -> bool:
        if isinstance(e, ColumnRef):
            return e.index < n_probe_cols
        if isinstance(e, ScalarFunc):
            return all(probe_only(a) for a in e.args)
        return True

    for d in agg.aggs:
        if d.distinct or d.name not in FUSABLE_AGGS:
            return False
        if not all(probe_only(a) for a in d.args):
            return False
    return True


def _chain_shape(build):
    """[scan, Sel*, Join(inner, unique, single-key, build=[scan, Sel*])]
    -> (outer_execs, inner_join) or None — the 3-table membership shape
    ops/joinagg.py's packed chain collapses (TPC-H Q3)."""
    if not build or not isinstance(build[0], (TableScan, IndexScan)):
        return None
    i = 1
    while i < len(build) and isinstance(build[i], Selection):
        i += 1
    if i != len(build) - 1 or not isinstance(build[i], Join):
        return None
    j = build[i]
    if j.join_type != "inner" or not j.build_unique:
        return None
    if len(j.probe_keys) != 1 or len(j.build_keys) != 1:
        return None
    inner = j.build
    if not inner or not isinstance(inner[0], (TableScan, IndexScan)):
        return None
    if not all(isinstance(e, Selection) for e in inner[1:]):
        return None
    return list(build[:i]), j


def _int_expr(e) -> bool:
    return e.ft.eval_type() == "int"


def _trace_packed_chain(ex, agg, comp, cols, valid, batches, cursor, group_capacity, join_capacity, state: _TraceState, topn_full, small_groups, unique_joins):
    """Packed-int fast path (ops/joinagg.py packed_join_groupsum): all
    eligibility is checked STATICALLY (expr FieldTypes) before any batch is
    consumed, so returning None never double-consumes a scan."""
    from ..ops.joinagg import _PACKED_AGGS, membership_chain, packed_join_groupsum

    for d in agg.aggs:
        if d.name not in _PACKED_AGGS or d.distinct:
            return None
        for a in d.args:
            if a.ft.eval_type() not in ("int", "decimal"):
                return None
    pk_e, bk_e = ex.probe_keys[0], ex.build_keys[0]
    if not _int_expr(pk_e) or not _int_expr(bk_e):
        return None
    if pk_e.ft.is_unsigned() != bk_e.ft.is_unsigned():
        raise TypeError("join key signedness mismatch (insert casts)")
    chain = _chain_shape(ex.build)
    simple = all(isinstance(e, Selection) for e in ex.build[1:]) and isinstance(ex.build[0], (TableScan, IndexScan))
    if chain is not None:
        outer_execs, ij = chain
        if not (_int_expr(ij.probe_keys[0]) and _int_expr(ij.build_keys[0])):
            return None
        if ij.probe_keys[0].ft.is_unsigned() != ij.build_keys[0].ft.is_unsigned():
            raise TypeError("join key signedness mismatch (insert casts)")
        # the next join's key must come from the OUTER scan's schema
        from ..expr.ir import ColumnRef, ScalarFunc

        outer_w = len(outer_execs[0].columns)

        def within(e, w):
            if isinstance(e, ColumnRef):
                return e.index < w
            if isinstance(e, ScalarFunc):
                return all(within(x, w) for x in e.args)
            return True

        if not within(bk_e, outer_w) or not within(ij.probe_keys[0], outer_w):
            return None
    elif not simple:
        return None

    # compile probe-side agg args (probe cols only — no consumption)
    garg_exprs = []
    for a in agg.aggs:
        garg_exprs.extend(a.args)
    avals = comp.run(list(garg_exprs), cols) if garg_exprs else []
    if any(a.value.ndim != 1 or a.raw is not None for a in avals):
        return None
    if len({id(a.null) for a in avals}) > 8:
        return None
    pkv = comp.run([pk_e], cols)[0]
    probe_ok = valid & ~pkv.null

    if chain is not None:
        outer_execs, ij = chain
        ocols, ovalid, ofts = _run_pipeline(outer_execs, batches, cursor, group_capacity, join_capacity, state, topn_full, small_groups, unique_joins)
        icols, ivalid, ifts = _run_pipeline(list(ij.build), batches, cursor, group_capacity, join_capacity, state, topn_full, small_groups, unique_joins)
        ocomp, icomp = ExprCompiler(ofts, state.params), ExprCompiler(ifts, state.params)
        okey = ocomp.run([ij.probe_keys[0]], ocols)[0]
        ckey = icomp.run([ij.build_keys[0]], icols)[0]
        payload = ocomp.run([bk_e], ocols)[0]
        o_ok = ovalid & ~okey.null & ~payload.null
        i_ok = ivalid & ~ckey.null
        hay_key, hay_ok, ovf = membership_chain(
            okey.value, o_ok, ckey.value, i_ok, payload.value,
        )
        state.join_overflow = state.join_overflow | ovf
        state.rows(hay_ok)  # inner join rows
    else:
        bcols, bvalid, bfts = _run_pipeline(list(ex.build), batches, cursor, group_capacity, join_capacity, state, topn_full, small_groups, unique_joins)
        bcomp = ExprCompiler(bfts, state.params)
        bkv = bcomp.run([bk_e], bcols)[0]
        hay_key = bkv.value
        hay_ok = bvalid & ~bkv.null

    aggs = []
    k = 0
    for a in agg.aggs:
        aggs.append((a, avals[k : k + len(a.args)]))
        k += len(a.args)
    states, group_valid, key_out, ovf, extent_cnt = packed_join_groupsum(
        hay_key, hay_ok, pkv, probe_ok, aggs,
    )
    state.join_overflow = state.join_overflow | ovf
    state.rows(jnp.where(group_valid, extent_cnt, jnp.int64(0)))
    new_cols: list[CompVal] = []
    for (a, av), st in zip(aggs, states):
        new_cols.extend(_agg_result_cols(a, av, st, group_valid, agg.partial))
    new_cols.append(key_out)
    return new_cols, group_valid, agg.output_fts()


def _trace_joinagg(agg, comp, cols, bkeys, pkeys, bvalid, valid, group_capacity, state: _TraceState):
    """Trace the fused join+agg kernel; None when a compiled arg shape is
    ineligible (multi-word value or raw string bytes riding the column)."""
    from ..ops.joinagg import join_stream_agg

    garg_exprs = []
    for a in agg.aggs:
        garg_exprs.extend(a.args)
    avals = comp.run(list(garg_exprs), cols) if garg_exprs else []
    if any(a.value.ndim != 1 or a.raw is not None for a in avals):
        return None
    aggs = []
    k = 0
    for a in agg.aggs:
        aggs.append((a, avals[k : k + len(a.args)]))
        k += len(a.args)
    res, sorted_aggs, group_out, j_ovf, join_rows = join_stream_agg(
        bkeys, pkeys, bvalid, valid, aggs, group_capacity,
    )
    state.join_overflow = state.join_overflow | j_ovf
    state.group_overflow = state.group_overflow | res.overflow
    state.rows(join_rows)
    new_cols: list[CompVal] = []
    for (a, av_s), st in zip(sorted_aggs, res.states):
        new_cols.extend(_agg_result_cols(a, av_s, st, res.group_valid, agg.partial))
    new_cols.extend(_gather([group_out], res.group_rep))
    return new_cols, res.group_valid, agg.output_fts()


def _check_join_key_types(pkeys: list[CompVal], bkeys: list[CompVal]):
    """Join keys must normalize to identical sort-key layouts; the planner
    is responsible for inserting casts (ref: hash join key unification in
    pkg/planner/core — e.g. decimal keys are brought to one scale)."""
    assert len(pkeys) == len(bkeys), "join key arity mismatch"
    for p, b in zip(pkeys, bkeys):
        pe, be = p.eval_type, b.eval_type
        if pe != be:
            raise TypeError(f"join key class mismatch: {pe} vs {be} (insert casts)")
        if pe == "decimal" and max(p.ft.decimal, 0) != max(b.ft.decimal, 0):
            raise TypeError("join key decimal scale mismatch (insert casts)")
        if pe == "int" and p.ft.is_unsigned() != b.ft.is_unsigned():
            raise TypeError("join key signedness mismatch (insert casts)")


def output_leaves(packed) -> list[tuple]:
    """Per output column of a program's `packed`, the leaves the host
    reads: a string column with raw bytes comes back as (null, bytes,
    lengths) and its packed compare words stay on the device; every other
    column as (values or packed words, null).  The program's epilogue
    sends these and `executor.decode_outputs` decodes these: both ask
    here."""
    return [tuple(out[1:]) if len(out) == 4 else tuple(out) for out in packed]


def _pack_cols(cols: list[CompVal]) -> list[tuple]:
    """CompVals -> the program's packed output tuples: (value, null) per
    column, raw string bytes + lengths riding along when present."""
    packed = []
    for c in cols:
        if c.raw is not None:
            packed.append((c.value, c.null, c.raw[0], c.raw[1]))
        else:
            packed.append((c.value, c.null))
    return packed


_STAGE_NAMES = {TableScan: "scan", IndexScan: "iscan", Selection: "sel", Projection: "proj",
                Limit: "limit", TopN: "topn", Sort: "sort", Window: "win"}
_JOIN_NAMES = {"inner": "join", "left_outer": "ljoin", "semi": "semijoin", "anti": "antijoin"}


def stage_name(ex) -> str:
    """The name of one executor inside a program: the `jax.named_scope`
    of its operations, and its part of the program's name."""
    if isinstance(ex, Aggregation):
        if not ex.group_by:
            return "agg"
        return "groupagg" if ex.aggs else "distinct"
    if isinstance(ex, Join):
        return _JOIN_NAMES[ex.join_type]
    return _STAGE_NAMES[type(ex)]


def program_name(dag: DAGRequest, vmap_batch: int | None = None, mesh_lanes: int | None = None,
                 mesh_devices: int | None = None) -> str:
    """What the jitted function is called, and with it the HLO module and
    the profiler's `PjitFunction(...)` event: the DAG's shape (executor
    chain of the probe pipeline) and the tier, e.g. `cop_scan_sel_agg`,
    `cop_scan_sel_sort_b8`.  Never a literal or an address: statements
    that differ in their constants share a name."""
    name = "cop_" + "_".join(stage_name(ex) for ex in dag.executors)
    if mesh_lanes is not None:
        return f"{name}_m{mesh_lanes}x{mesh_devices or 1}"
    return name if vmap_batch is None else f"{name}_b{vmap_batch}"


def build_program(
    dag: DAGRequest,
    capacities,
    group_capacity: int = DEFAULT_GROUP_CAPACITY,
    join_capacity: int | None = None,
    topn_full: bool = False,
    small_groups: int | None = None,
    unique_joins: bool = True,
    vmap_batch: int | None = None,
    mesh_lanes: int | None = None,
    mesh_devices: int | None = None,
    mesh_kind: str | None = None,
    radix_joins: bool = True,
    mesh_root: bool = False,
) -> CompiledDAG:
    """Compile the whole DAG tree (probe pipeline + all join build
    pipelines) into one fused XLA program over a tuple of device batches.

    vmap_batch=B builds the REGION-BATCHED variant: the first (probe) batch
    carries a leading region axis of size B (chunk.device
    to_stacked_device_batch) and the program vmaps over it, so B regions
    execute in ONE XLA launch — the batch-coprocessor analog of TiFlash
    serving all of a store's regions from one request
    (ref: copr/batch_coprocessor.go). Join build sides arriving as broadcast
    aux batches are shared across regions (in_axes=None), exactly like the
    broadcast join operand every region task carries. All outputs (packed
    columns, valid, n_rows, the overflow flags, ex_rows) gain a leading
    region axis; overflow is therefore PER REGION and the driver can retry
    only the lanes that overflowed.

    mesh_lanes=R builds the MESH variant (the dispatch planner's top tier):
    the region-stacked batch additionally SHARDS its leading axis over a
    `mesh_devices`-wide 1-D device mesh under shard_map, each device vmaps
    the per-region program over its local lanes, and the per-region results
    merge ON DEVICE per `mesh_kind` — partial aggregate states psum/pmin/
    pmax-reduced over the region axis ("scalar"), group-state tables
    all_gathered and re-aggregated in merge mode ("group"), or top-k
    candidates all_gathered and re-topped ("topn") — so the program returns
    ONE merged result instead of R per-region partials (SURVEY §3.1/§5).
    Mesh outputs: (merged packed cols, merged valid, per-lane ex_rows
    [R, n_exec], overflow scalar); overflow is GLOBAL — the driver falls
    back to the vmapped tier, whose per-lane ladder takes over.

    mesh_root: `dag` is the statement's UNSPLIT DAG and the mesh program
    finishes the statement. `split_dag` cuts the traced shape in two: the
    lanes run the pushdown half, and behind the on-device merge the one
    merged state goes on, inside the same shard_map body, through the
    root's half (the Final re-group in place of the Partial2 one, HAVING,
    TopN / Sort / Limit, the projection, the statement's output offsets),
    so the outputs are the statement's rows, replicated. Both halves read
    the one operand set of the one walk: the key is the unsplit DAG's
    `program_key()`, and the name holds the root's stages too
    (`cop_scan_sel_groupagg_sort_m8x4`)."""
    if isinstance(capacities, int):
        capacities = (capacities,)
    capacities = tuple(capacities)
    n_scans = len(collect_scans(dag.executors))
    assert len(capacities) == n_scans, f"need {n_scans} batch capacities, got {len(capacities)}"
    join_capacity = join_capacity or max(capacities)
    # what is traced is the plan's shape; the values of the DAG that
    # happens to build the program are arguments like any later DAG's
    dag, _key, operands = dag.parameterized()
    lanes = operand_lanes(operands)  # which operand a `Param`'s lane names
    lane_dag, root_dag = dag, None  # what a lane runs; what follows the mesh merge
    if mesh_root:
        from ..distsql.root import split_dag

        plan = split_dag(dag)
        lane_dag, root_dag = plan.push_dag, plan.root_dag
        assert mesh_lanes is not None and root_dag is not None, "mesh_root needs a mesh program and a statement with a root half"

    radix_info: dict = {}

    def program(*args):
        batches = args[:n_scans]
        state = _TraceState(params=dict(zip(lanes, args[n_scans:])))
        state.radix_joins = radix_joins
        cursor = [0]
        cols, valid, _ = _run_pipeline(lane_dag.executors, batches, cursor, group_capacity, join_capacity, state, topn_full, small_groups, unique_joins, out_offsets=lane_dag.output_offsets)
        packed = _pack_cols([cols[i] for i in lane_dag.output_offsets])
        n_out = valid.sum()
        # a plan that recorded no count: no constant/empty-shaped stand-in
        # — both a 0-length output and a folded-constant output have
        # SIGSEGV'd the TPU compiler (2026-07-31); reuse the
        # (data-dependent) row count
        ex = jnp.stack(state.ex_rows) if state.ex_rows else n_out[None].astype(jnp.int64)
        radix_info.update(state.radix_meta)  # trace-time side channel
        # the flag tuple carries the capacity NEED hints and the radix
        # escape count so the retry driver / attribution read them in the
        # SAME device fetch as the overflow flags (no extra round-trip)
        ovfs = (state.group_overflow, state.join_overflow, state.topn_overflow,
                state.group_need, state.join_need, state.radix_escapes)
        return packed, valid, n_out, ovfs, ex

    if mesh_lanes is not None:
        fn = _build_mesh_fn(lane_dag, program, n_scans, lanes, mesh_lanes,
                            mesh_devices or 1, mesh_kind, group_capacity,
                            root_dag, small_groups)
    elif vmap_batch is not None:
        # region axis on the probe batch only; aux/build batches and the
        # operands broadcast: a group holds requests of one fingerprint,
        # so one set of values serves every lane
        fn = jax.vmap(program, in_axes=(0,) + (None,) * (n_scans - 1 + len(lanes)))
    else:
        fn = program
    fn.__name__ = fn.__qualname__ = program_name(dag, vmap_batch, mesh_lanes, mesh_devices)
    # in every variant the output columns come first, and the host reads all the rest
    outputs = launch.HostOutputs(fn, lambda out: (output_leaves(out[0]), *out[1:]))
    return CompiledDAG(outputs, fn, dag.output_fts(), capacities, group_capacity, join_capacity,
                       radix_info=radix_info)


def _build_mesh_fn(dag: DAGRequest, program, n_scans: int, param_lanes: tuple, lanes: int,
                   n_devices: int, kind: str, group_capacity: int,
                   root: DAGRequest | None = None, small_groups: int | None = None):
    """shard_map wrapper: vmap the per-region program over each device's
    local lanes, then merge the per-region results on device (psum of
    partial states / merge-mode re-group / re-top-k) — the mesh tier's
    program body. `lanes` must divide over `n_devices` (the store pads the
    region axis with empty lanes).

    `root` (the root half of the split whose pushdown half `dag` is) makes
    the body go on where the merged state is: the gathered rows, or the
    psum-merged scalar states, are the root pipeline's scan output, so its
    first executor is the Final re-group (or the re-top-k) that the merge
    stage would have run in Partial2 mode, and the rest of the statement
    follows through the same `_run_pipeline` a one-chip program uses.
    TopN there is the exact full sort: the rows are few, and the tail has
    no retry ladder (any overflow is the program's one global flag)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import REGION_AXIS, merge_packed_states, region_mesh

    assert kind in ("scalar", "group", "topn"), f"unknown mesh kind {kind!r}"
    assert lanes % n_devices == 0, "mesh lanes must divide over the devices"
    mesh = region_mesh(n_devices)
    last = dag.executors[-1]
    out_fts = dag.output_fts()

    def device_fn(local, *aux):
        params = dict(zip(param_lanes, aux[n_scans - 1:]))
        packed, valid, _n, ovfs, ex = jax.vmap(lambda b: program(b, *aux))(local)
        local_ovf = ovfs[0].any() | ovfs[1].any() | ovfs[2].any()
        # radix escape total over the region axis (join_radix attribution
        # — the mesh tier reports it like the other tiers)
        radix_esc = jax.lax.psum(ovfs[5].sum(), REGION_AXIS)
        m_ovf = jnp.bool_(False)
        if kind == "scalar":
            # the north-star collective: partial states psum/pmin/pmax-
            # reduced over the region axis (parallel/mesh.py merge seam)
            cols = [CompVal(v, nl, ft) for (v, nl), ft in
                    zip(merge_packed_states(list(last.aggs), packed), out_fts)]
            mvalid = jnp.ones(1, bool)
        else:
            cols, mvalid = _gather_mesh_outputs(packed, valid, out_fts)
            if root is not None:
                pass  # its first executor re-groups (Final) or re-tops the gathered rows
            elif kind == "group":
                cols, mvalid, m_ovf = _mesh_merge_group(
                    last, out_fts, cols, mvalid, group_capacity, params)
            else:
                cols, mvalid, m_ovf = _mesh_merge_topn(last, out_fts, cols, mvalid, params)
        if root is not None:
            tail = _TraceState(params)
            cols, mvalid, _ = _run_pipeline(
                root.executors, (), [0], group_capacity, 0, tail, topn_full=True,
                small_groups=small_groups, rows=(cols, mvalid, out_fts))
            cols = [cols[i] for i in root.output_offsets]
            m_ovf = tail.group_overflow | tail.join_overflow | tail.topn_overflow
        merged = _pack_cols(cols)
        ovf = jax.lax.pmax((local_ovf | m_ovf).astype(jnp.int32), REGION_AXIS) > 0
        return merged, mvalid, ex, ovf, radix_esc

    fn = jax.shard_map(
        device_fn,
        mesh=mesh,
        # prefix specs: the whole stacked probe batch shards its leading
        # region axis; aux (join build) batches and the operands replicate
        # to every device
        in_specs=(P(REGION_AXIS),) + (P(),) * (n_scans - 1 + len(param_lanes)),
        # merged cols / valid / overflow / escape count are replicated in
        # fact (psum / all_gather-then-identical-local-work) but not
        # statically inferrable by the vma check; ex_rows keep their
        # region axis
        out_specs=(P(), P(), P(REGION_AXIS), P(), P()),
        check_vma=False,
    )
    return fn


def _gather_mesh_outputs(packed, valid, out_fts):
    """Flatten the vmapped per-lane outputs [R_local, L, ...] to rows and
    all_gather them over the mesh: every device ends with the SAME
    [R_total*L] row block (device-major == region stack == task order), so
    the merge stage below computes a replicated result with no further
    communication. Raw string bytes ride whole — byte-exact, no packed-word
    truncation."""
    from ..parallel.mesh import REGION_AXIS

    cols = []
    for out, ft in zip(packed, out_fts):
        flat = []
        for a in out:
            rows = a.reshape((-1,) + a.shape[2:])
            g = jax.lax.all_gather(rows, REGION_AXIS)
            flat.append(g.reshape((-1,) + g.shape[2:]))
        if len(out) == 4:
            cols.append(CompVal(flat[0], flat[1], ft, raw=(flat[2], flat[3])))
        else:
            cols.append(CompVal(flat[0], flat[1], ft))
    gvalid = jax.lax.all_gather(valid.reshape(-1), REGION_AXIS).reshape(-1)
    return cols, gvalid


def _mesh_merge_group(agg, state_fts, cols, valid, group_capacity: int, params: dict):
    """Device-side merge of the gathered per-region group tables: the root
    Final merge's Partial2 re-group (root.py _merge_aggregation, partial
    output) traced INTO the mesh program — the output schema is the push
    DAG's partial schema again, so one merged table per store replaces R
    per-region tables while the root's Final pass runs unchanged. A mesh
    program that carries the root's half (`_build_mesh_fn(root=)`) skips
    this stage: the root pipeline's own Final re-group reads the gathered
    rows."""
    from dataclasses import replace as _replace

    from ..distsql.root import _merge_aggregation

    p2 = _replace(_merge_aggregation(agg), partial=True)
    comp = ExprCompiler(state_fts, params)
    gvals = comp.run(list(p2.group_by), cols)
    garg_exprs = [a for d in p2.aggs for a in d.args]
    avals = comp.run(garg_exprs, cols) if garg_exprs else []
    aggs = []
    k = 0
    for d in p2.aggs:
        aggs.append((d, avals[k: k + len(d.args)]))
        k += len(d.args)
    res = group_aggregate(gvals, aggs, valid, group_capacity, merge=True)
    new_cols: list[CompVal] = []
    for (d, av), st in zip(aggs, res.states):
        new_cols.extend(_agg_result_cols(d, av, st, res.group_valid, True))
    new_cols.extend(_gather(gvals, res.group_rep))
    return new_cols, res.group_valid, res.overflow


def _mesh_merge_topn(ex, fts, cols, valid, params: dict):
    """Device-side re-top-k over the gathered per-region candidates
    (global top-k ⊆ union of per-region top-k): the order expressions
    recompute over the candidate rows — TopN preserves its input schema,
    so the same exprs apply. full_sort: the candidate block is tiny
    (R*k rows) and the exact variant never overflows."""
    comp = ExprCompiler(fts, params)
    order_vals = comp.run([e for e, _ in ex.order_by], cols)
    by = list(zip(order_vals, [d for _, d in ex.order_by]))
    idx, out_valid, _ovf = topn(by, valid, ex.limit, full_sort=True)
    return _gather(cols, idx), out_valid, jnp.bool_(False)


def _agg_result_cols(a, av: list[CompVal], st, group_valid, partial: bool) -> list[CompVal]:
    """One aggregate's output columns from its states.

    GatherState (first_row any mode, string min/max): gather the value
    column — raw string bytes ride along — from the original rows; the wire
    state for partial first_row is [has, value] (expr/agg.py schema)."""
    if isinstance(st, GatherState):
        has = st.has & group_valid
        g = _gather([av[-1]], st.idx)[0]
        null = g.null | ~has
        out = []
        if a.name == "first_row" and partial:
            out.append(CompVal(has.astype(jnp.int64), jnp.zeros(has.shape, bool), a.partial_fts()[0]))
        out.append(CompVal(g.value, null, a.ft, raw=g.raw))
        return out
    fts = a.partial_fts()
    if partial:
        return [CompVal(v, nl, ft) for (v, nl), ft in zip(st, fts)]
    v, nl = finalize_agg(a, st, group_valid)
    return [CompVal(v, nl, a.ft)]


class ProgramCache:
    """Program key -> CompiledDAG (ref: coprocessor cache keying).  The
    key is `dag.program_key()`, the plan's shape: requests that differ in
    parameterisable literals or in the table they scan share an entry.

    The key includes the region-batch size (`vmap_batch`): a vmapped
    program is specialized to its leading axis, so a new batch shape is an
    honest recompile, not a hit — `stats()` exposes per-instance
    compiles/hits so tests can assert "one compile + N hits per batch
    shape" (the launch-count regression guard).

    Compiles are single-flight per key: pool-tier region tasks all need
    the same push program on a cold cache, and without coordination each
    thread that misses compiles its own copy (correct but N× the compile
    cost, and the compiles/hits counters — the regression guard — become
    timing-dependent). The first thread to miss claims the key; racers
    wait on its event and land as hits."""

    def __init__(self):
        import threading

        # _cache is deliberately unguarded: dict get/set are GIL-atomic
        self._cache: dict = {}
        self._stats_mu = threading.Lock()  # pool threads share one cache
        self.compiles = 0  # guarded_by: _stats_mu
        self.hits = 0  # guarded_by: _stats_mu
        self._inflight: dict = {}  # key -> Event, guarded_by: _stats_mu
        self._input_rungs: dict = {}  # (program key, input) -> rows; unguarded like _cache: a lost race costs one more rung

    def input_capacity(self, dag: DAGRequest, i: int, rows: int) -> int:
        """The row capacity at which input `i` of a plan shape is handed
        to its program by a driver whose input is another program's
        output (`run_dag_on_chunks`: a root merge over the regions'
        groups), so that its size moves with the statement's literals.
        The rung is sticky and picked with room: the first input of
        `rows` rows gets the ladder's rung (exec/ladder.py) that holds
        twice as many, and every later input that fits rides the same
        rung, so statements of one shape whose row counts stay within a
        factor of two of the first build one program (TPC-H Q3's 212-283
        groups at SF 0.02 straddle 256)."""
        key = (dag.program_key(), i)
        have = self._input_rungs.get(key)
        if have is not None and rows <= have:
            return have
        cap = self._input_rungs[key] = rung_for(2 * rows)
        return cap

    def get(
        self,
        dag: DAGRequest,
        capacities,
        group_capacity: int = DEFAULT_GROUP_CAPACITY,
        join_capacity: int | None = None,
        topn_full: bool = False,
        small_groups: int | None = None,
        unique_joins: bool = True,
        vmap_batch: int | None = None,
        mesh_lanes: int | None = None,
        mesh_devices: int | None = None,
        mesh_kind: str | None = None,
        radix_joins: bool = True,
        mesh_root: bool = False,
    ) -> CompiledDAG:
        return self.get_info(dag, capacities, group_capacity, join_capacity,
                             topn_full, small_groups, unique_joins, vmap_batch,
                             mesh_lanes, mesh_devices, mesh_kind, radix_joins, mesh_root)[0]

    def get_info(
        self,
        dag: DAGRequest,
        capacities,
        group_capacity: int = DEFAULT_GROUP_CAPACITY,
        join_capacity: int | None = None,
        topn_full: bool = False,
        small_groups: int | None = None,
        unique_joins: bool = True,
        vmap_batch: int | None = None,
        mesh_lanes: int | None = None,
        mesh_devices: int | None = None,
        mesh_kind: str | None = None,
        radix_joins: bool = True,
        mesh_root: bool = False,
    ) -> tuple:
        """(program, cache_hit, compile_ns) — the attribution triple the
        exec summaries and the TRACE span tree surface (ref: the
        coprocessor-cache hit flag in copr responses)."""
        if isinstance(capacities, int):
            capacities = (capacities,)
        capacities = tuple(capacities)
        from ..ops.dense_pallas import pallas_mode

        # pallas mode is read at TRACE time (env + backend): a program
        # traced under one mode must not serve another (mismatched
        # buffer counts at execution)
        # mesh programs are specialized to their lane count AND device
        # count (shard_map shapes both into the trace); mesh_kind is
        # derivable from the key but cheap to carry explicitly; mesh_root
        # says the key's DAG is the unsplit statement, its root half traced
        # behind the mesh merge
        key = (dag.program_key(), capacities, group_capacity, join_capacity, topn_full, small_groups, unique_joins, vmap_batch, pallas_mode(), mesh_lanes, mesh_devices, mesh_kind, radix_joins, mesh_root)
        return self.built(
            key,
            lambda: build_program(dag, capacities, group_capacity, join_capacity, topn_full, small_groups, unique_joins, vmap_batch=vmap_batch,
                                  mesh_lanes=mesh_lanes, mesh_devices=mesh_devices, mesh_kind=mesh_kind, radix_joins=radix_joins,
                                  mesh_root=mesh_root),
            batch_size=vmap_batch, mesh_lanes=mesh_lanes)

    def built(self, key: tuple, build, **attrs) -> tuple:
        """(program, cache_hit, compile_ns) of `key`, `build()` being called
        by the one thread that finds it missing: the counted, single-flight
        half of `get_info`, which every compiled program of the engine goes
        through — the cop programs above and the exchange programs of
        `mpp/exchange_op.py`, whose key is the plan's shape too.  `attrs`
        that are not None go onto the `exec.program` span of a miss."""
        import threading
        import time as _t

        from ..util import metrics, tracing

        while True:
            prog = self._cache.get(key)
            if prog is not None:
                with self._stats_mu:
                    self.hits += 1
                metrics.PROGRAM_CACHE_HITS.inc()
                with tracing.span("exec.program", cache_hit=True):
                    pass
                return prog, True, 0
            with self._stats_mu:
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = threading.Event()
                    break  # this thread owns the compile
            # another thread is compiling this key: wait, then re-read the
            # cache (if its compile raised, the next waiter claims the key)
            ev.wait()
        try:
            with tracing.span("exec.program", cache_hit=False) as sp:
                with self._stats_mu:
                    self.compiles += 1
                metrics.PROGRAM_COMPILES.inc()
                t0 = _t.perf_counter_ns()
                prog = build()
                compile_ns = _t.perf_counter_ns() - t0  # the Python closure only: JAX traces and compiles at the first call (exec/launch.py)
                if sp is not None:
                    sp.set("compile_ns", compile_ns)
                    for k, v in attrs.items():
                        if v is not None:
                            sp.set(k, v)
            self._cache[key] = prog
            metrics.PROGRAM_CACHE_ENTRIES.set(len(self._cache))
        finally:
            with self._stats_mu:
                self._inflight.pop(key).set()
        return prog, False, compile_ns

    def stats(self):
        with self._stats_mu:
            return {"entries": len(self._cache), "compiles": self.compiles, "hits": self.hits}
