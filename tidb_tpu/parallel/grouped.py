"""Grouped aggregation over the mesh — the MPP partial/exchange/final
pipeline as ONE shard_map program (ref: unistore/cophandler/mpp_exec.go
aggExec:999 below exchSenderExec:609, receiver-side final agg above
exchRecvExec:723; fragment planning pkg/planner/core/fragment.go:116).

Per device, in a single fused XLA computation:
  1. flatten the device's local regions into one row block, run the scan
     expressions + selection,
  2. Partial1 group aggregation (sort/segment kernel) -> a local group-state
     table [G_local],
  3. hash-partition the group states by group key and `all_to_all` them over
     the ICI mesh — every device ends up owning one hash partition of the
     global group space (ref: ExchangeSender Hash mode, fnv64 row hash),
  4. merge-mode group aggregation over the owned states -> FINAL values for
     the owned groups. No host round-trip between phases.

The host wrapper gathers the per-device final tables and decodes one result
Chunk. Group keys AND string aggregate values (min/max/first_row over
varchar) travel as packed compare words (first 32 bytes — the SQL gate
rejects wider string columns)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..chunk.device import DeviceBatch
from ..exec.dag import Aggregation, DAGRequest, Selection, operand_lanes
from ..expr.compile import CompVal, ExprCompiler, normalize_device_column
from ..ops import apply_selection, group_aggregate
from ..ops.aggregate import GatherState, finalize_agg
from ..mpp.exchange_op import exchange_arrays, hash_partition_ids
from .mesh import REGION_AXIS


def _flatten_local(local: DeviceBatch):
    """[R_local, cap] region-stacked batch -> flat [R_local*cap] columns."""
    cols = []
    for c in local.cols:
        data = c.data.reshape((-1,) + c.data.shape[2:])
        null = c.null.reshape(-1)
        length = c.length.reshape(-1) if c.length is not None else None
        cols.append(type(c)(data, null, length, c.ft))
    return cols, local.row_valid.reshape(-1)


def _materialize_gather(desc, arg_vals, st: GatherState, final: bool = False):
    """GatherState -> concrete state columns. Partial form keeps the
    [has, value] wire schema for first_row; `final` collapses to the single
    result column. String values (first_row/min/max over varchar) ride the
    exchange as their packed compare words [G, W+1] — decode_outputs
    reconstructs the bytes, so strings up to STRING_WORDS*8 bytes survive
    (the SQL gate parallel/sql.py rejects wider columns)."""
    vcol = arg_vals[-1]
    if vcol.value.ndim == 2:
        val = jnp.where(st.has[:, None], vcol.value[st.idx, :], jnp.zeros((), vcol.value.dtype))
    else:
        val = jnp.where(st.has, vcol.value[st.idx], jnp.zeros((), vcol.value.dtype))
    null = jnp.where(st.has, vcol.null[st.idx], True)
    if desc.name == "first_row" and not final:
        return [(st.has.astype(jnp.int64), jnp.zeros(st.has.shape, bool)), (val, null)]
    return [(val, null)]


def agg_exchange_phases(agg, schema_fts, cvals, valid, n_parts: int, group_capacity: int, bcap: int, extra_overflow=None,
                        params: dict | None = None):
    """The MPP partial/exchange/final pipeline given the pre-agg schema —
    phases 1-3 of the module docstring. Called inside shard_map by both the
    scan+sel path (run_sharded_grouped_agg) and the hash-shuffle join path
    (mpp/exchange_op.py run_exchange_join_agg); `params` are the program's
    operands by lane (`ExprCompiler`). Returns the flat output tuple
    [group_valid, (value, null)*, need, overflow]: `need` is the most
    groups any device's partial or final table had to hold (the sort
    kernel counts them past capacity; exec/ladder.py), so an overflow
    retry jumps to the rung that holds them."""
    comp = ExprCompiler(schema_fts, params)
    gvals = comp.run(list(agg.group_by), cvals)
    arg_exprs = [a for d in agg.aggs for a in d.args]
    avals = comp.run(arg_exprs, cvals) if arg_exprs else []
    aggs = []
    k = 0
    for d in agg.aggs:
        aggs.append((d, avals[k : k + len(d.args)]))
        k += len(d.args)

    if any(d.distinct for d in agg.aggs):
        # DISTINCT is not state-decomposable, but it IS local-exact after
        # the group-key shuffle: every group lands whole on one device
        # (the reference's MPP plan for distinct aggs shuffles raw rows by
        # group key then aggregates Complete-mode on the owner —
        # planner/core/task.go agg-over-exchange with one phase)
        return _distinct_exchange_phases(
            agg, gvals, aggs, valid, n_parts, group_capacity, bcap, extra_overflow
        )

    # -- phase 1: local Partial1 ------------------------------------
    res = group_aggregate(gvals, aggs, valid, group_capacity, merge=False)
    p1_overflow = res.overflow
    state_cols: list[tuple] = []  # flat (value, null) per state column
    state_fts: list = []
    for (d, av), st in zip(aggs, res.states):
        if isinstance(st, GatherState):
            mat = _materialize_gather(d, av, st)
        else:
            mat = st
        state_cols.extend(mat)
        state_fts.extend(d.partial_fts())
    gkey_cols = []
    for gv in gvals:
        if gv.value.ndim == 2:
            gkey_cols.append((gv.value[res.group_rep, :], gv.null[res.group_rep]))
        else:
            gkey_cols.append((gv.value[res.group_rep], gv.null[res.group_rep]))
    gvalid = res.group_valid

    # -- phase 2: hash-exchange the group-state rows (exchange_op) ----
    key_cvs = [
        CompVal(v, nl, g.ft) for (v, nl), g in zip(gkey_cols, agg.group_by)
    ]
    part = hash_partition_ids(key_cvs, n_parts)
    flat_arrays = [a for v, nl in state_cols + gkey_cols for a in (v, nl)]
    flat, fvalid, ex_overflow = exchange_arrays(flat_arrays, gvalid, part, n_parts, bcap)

    # -- phase 3: merge-mode aggregation on the owned partition ------
    n_state = len(state_cols)
    it = iter(range(0, 2 * n_state, 2))
    owned_states = [(flat[i], flat[i + 1].astype(bool)) for i in it]
    base = 2 * n_state
    owned_gkeys = [
        CompVal(flat[base + 2 * j], flat[base + 2 * j + 1].astype(bool), g.ft)
        for j, g in enumerate(agg.group_by)
    ]
    merge_aggs = []
    si = 0
    for d, _ in aggs:
        n = len(d.partial_fts())
        args = [
            CompVal(owned_states[si + i][0], owned_states[si + i][1], state_fts[si + i])
            for i in range(n)
        ]
        merge_aggs.append((d, args))
        si += n
    fin = group_aggregate(owned_gkeys, merge_aggs, fvalid, group_capacity, merge=True)
    f_overflow = fin.overflow

    out_cols = []
    for (d, av), st in zip(merge_aggs, fin.states):
        if isinstance(st, GatherState):
            st = GatherState(st.idx, st.has & fin.group_valid)
            out_cols.extend(_materialize_gather(d, av, st, final=True))
        else:
            v, nl = finalize_agg(d, st, fin.group_valid)
            out_cols.append((v, nl))
    for gk in owned_gkeys:
        if gk.value.ndim == 2:
            out_cols.append((gk.value[fin.group_rep, :], gk.null[fin.group_rep] | ~fin.group_valid))
        else:
            out_cols.append((gk.value[fin.group_rep], gk.null[fin.group_rep] | ~fin.group_valid))
    local_ovf = p1_overflow | ex_overflow | f_overflow
    if extra_overflow is not None:
        local_ovf = local_ovf | extra_overflow
    overflow = jax.lax.pmax(local_ovf.astype(jnp.int32), REGION_AXIS) > 0
    flat_out = [a for v, nl in out_cols for a in (v, nl)]
    return tuple([fin.group_valid] + flat_out + [_need(res, fin), overflow])


def _distinct_exchange_phases(agg, gvals, aggs, valid, n_parts: int, group_capacity: int, bcap: int, extra_overflow=None):
    """Raw-row exchange + Complete-mode owner aggregation (DISTINCT path).

    Exchanges (group keys ++ agg args) row-wise instead of partial states;
    the owner runs the single-device group kernel in Complete mode, whose
    hash-distinct machinery (ops/aggregate.py _distinct_states) is exact.
    Output layout matches agg_exchange_phases."""
    part = hash_partition_ids(gvals, n_parts)
    row_cvs = list(gvals) + [a for _, avs in aggs for a in avs]
    flat_arrays = [a for cv in row_cvs for a in (cv.value, cv.null)]
    flat, fvalid, ex_overflow = exchange_arrays(flat_arrays, valid, part, n_parts, bcap)

    k = 0
    owned: list[CompVal] = []
    for cv in row_cvs:
        owned.append(CompVal(flat[k], flat[k + 1].astype(bool), cv.ft))
        k += 2
    o_gvals = owned[: len(gvals)]
    o_args = owned[len(gvals):]
    o_aggs = []
    ai = 0
    for d, avs in aggs:
        o_aggs.append((d, o_args[ai : ai + len(avs)]))
        ai += len(avs)
    fin = group_aggregate(o_gvals, o_aggs, fvalid, group_capacity, merge=False)

    out_cols = []
    for (d, av), st in zip(o_aggs, fin.states):
        if isinstance(st, GatherState):
            st = GatherState(st.idx, st.has & fin.group_valid)
            out_cols.extend(_materialize_gather(d, av, st, final=True))
        else:
            v, nl = finalize_agg(d, st, fin.group_valid)
            out_cols.append((v, nl))
    for gk in o_gvals:
        if gk.value.ndim == 2:
            out_cols.append((gk.value[fin.group_rep, :], gk.null[fin.group_rep] | ~fin.group_valid))
        else:
            out_cols.append((gk.value[fin.group_rep], gk.null[fin.group_rep] | ~fin.group_valid))
    local_ovf = ex_overflow | fin.overflow
    if extra_overflow is not None:
        local_ovf = local_ovf | extra_overflow
    overflow = jax.lax.pmax(local_ovf.astype(jnp.int32), REGION_AXIS) > 0
    flat_out = [a for v, nl in out_cols for a in (v, nl)]
    return tuple([fin.group_valid] + flat_out + [_need(fin), overflow])


def _need(*results) -> jax.Array:
    """The largest group count any of `results` (GroupAggResult) had to
    hold on any device; 0 where no kernel counted past its capacity."""
    needs = [r.need for r in results if r.need is not None]
    if not needs:
        return jnp.int32(0)
    local = needs[0]
    for n in needs[1:]:
        local = jnp.maximum(local, n)
    # int32: the TPU lowers a max all-reduce of 32-bit words only; a count
    # of rows a device holds fits
    return jax.lax.pmax(local.astype(jnp.int32), REGION_AXIS)


def tail_phase(outs: tuple, agg, tail, params: dict) -> tuple:
    """The statement's tail behind the final aggregate, in the exchange
    program (mpp/fragment.py `split_tail`, `tail_in_program`): HAVING
    selections narrow each device's owned final groups, projections
    compute over them, as the root would over the gathered groups.
    `outs` is `agg_exchange_phases`' tuple; returns [groups before the
    tail, rows after it, (value, null)* of the tail's schema, need,
    overflow]."""
    gvalid, flat, need, overflow = outs[0], outs[1:-2], outs[-2], outs[-1]
    fts = agg.output_fts()
    cols = [CompVal(flat[2 * i], flat[2 * i + 1], ft) for i, ft in enumerate(fts)]
    valid = gvalid
    for ex in tail or ():
        comp = ExprCompiler(fts, params)
        if isinstance(ex, Selection):
            valid = apply_selection(valid, comp.run(list(ex.conditions), cols))
        else:
            cols = comp.run(list(ex.exprs), cols)
            fts = [e.ft for e in ex.exprs]
    flat_out = [a for c in cols for a in (c.value, c.null | ~valid)]
    return tuple([gvalid, valid] + flat_out + [need, overflow])


def run_sharded_grouped_agg(
    dag: DAGRequest,
    stacked: DeviceBatch,
    mesh,
    group_capacity: int = 1024,
    bucket_cap: int | None = None,
    programs=None,
    stats: dict | None = None,
):
    """Execute TableScan [Selection] Aggregation(group_by) [tail] over a
    region-sharded mesh; returns (chunk, overflow flag).

    The Aggregation node is taken as the LOGICAL (Complete-mode) shape; the
    partial/final split happens inside. Output chunk layout matches the
    single-chip executor: [agg results..., group keys...], or the tail's
    schema where the DAG goes on behind the aggregate with Selections and
    Projections (`mpp/fragment.py` `split_tail`): they run over each
    device's final groups in the same program. `stats`, where given,
    receives `need` (the ladder's hint) and `groups` (final groups before
    the tail)."""
    from ..mpp.fragment import split_tail

    head, tail = split_tail(dag)
    agg = head.executors[-1]
    assert isinstance(agg, Aggregation) and agg.group_by, "grouped mesh agg needs GROUP BY"
    if any(d.name == "group_concat" for d in agg.aggs):
        raise NotImplementedError("group_concat on mesh (root-only, oracle-evaluated)")
    input_fts = [c.ft for c in dag.scan().columns]
    n_parts = mesh.devices.size
    bcap = bucket_cap or group_capacity
    # the traced DAG is the plan's shape; its constants are operands
    shape, _key, operands = dag.parameterized()
    lanes = operand_lanes(operands)
    s_head, s_tail = split_tail(shape)

    def device_fn(local: DeviceBatch, *ops):
        params = dict(zip(lanes, ops))
        cols, valid = _flatten_local(local)
        cvals = [normalize_device_column(c) for c in cols]
        for ex in s_head.executors[1:-1]:
            if isinstance(ex, Selection):
                conds = ExprCompiler(input_fts, params).run(list(ex.conditions), cvals)
                valid = apply_selection(valid, conds)
            else:
                raise TypeError(f"mesh pipeline supports scan+selection+agg, got {ex}")
        outs = agg_exchange_phases(s_head.executors[-1], input_fts, cvals, valid, n_parts, group_capacity, bcap,
                                   params=params)
        return tail_phase(outs, s_head.executors[-1], s_tail, params)

    spec_batch = jax.tree.map(lambda _: P(REGION_AXIS), stacked)
    from ..mpp.exchange_op import run_exchange_program
    from .mesh import decode_group_mesh_outputs, group_mesh_out_spec

    out_fts = tail_fts(agg, tail)
    outs, fetch = run_exchange_program(
        "mesh_exchange_group_agg", dag, mesh,
        lambda: jax.shard_map(device_fn, mesh=mesh, in_specs=(spec_batch,) + (P(),) * len(lanes),
                              out_specs=group_mesh_out_spec(len(out_fts)), check_vma=False),
        (group_capacity, bcap), (stacked,), programs)
    # decode: the tail's schema (Complete-mode [aggs..., keys...] without
    # one) — the shared seam (mesh.py) both grouped paths use
    return decode_group_mesh_outputs(outs, fetch, out_fts, stats)


def tail_fts(agg, tail) -> list:
    """The schema an exchange program hands back: the aggregate's
    Complete-mode output, then as each tail Projection makes it."""
    fts = list(agg.output_fts())
    for ex in tail or ():
        if not isinstance(ex, Selection):
            fts = [e.ft for e in ex.exprs]
    return fts
