"""Mesh data parallelism: regions sharded over TPU devices.

The reference fans per-region cop tasks out to store nodes over gRPC
(ref: copr/coprocessor.go:806 worker pool; batch_coprocessor.go groups
regions per store). The TPU-native shape (SURVEY.md §2.5): stack region
batches on a leading axis, shard that axis over a 1-D `jax.sharding.Mesh`,
run the fused DAG per region under `shard_map` + `vmap`, and psum the
partial aggregate states over ICI — the collective replaces the host-side
merge loop, which is the BASELINE.json north star:

    "per-region partial aggregates are psum-reduced over the ICI mesh
     before final merge"

This module owns the SHARED merge seam: `partial_merge_plan` +
`merge_packed_states` (psum for sum/count/avg/moments, pmin/pmax with the
flipped unsigned domain, all_gather for bit/first states) are consumed both
by the standalone `run_sharded_partial_agg` entry point and by
`exec/builder.py`'s mesh-tier programs, so the standard `distsql.select`
dispatch and the exchange programs of mpp/ merge states with ONE
implementation. Region stacking likewise delegates to the chunk layer's
`to_stacked_device_batch` — the same host-side stacking the batch
coprocessor uses — instead of a second device-side stack.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..chunk import Chunk
from ..chunk.device import DeviceBatch, to_stacked_device_batch
from ..mpp.exchange_op import REGION_AXIS  # canonical home (ISSUE 18)


def region_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (REGION_AXIS,))


def stack_region_batches(chunks: list[Chunk], capacity: int | None = None, n_total: int | None = None) -> DeviceBatch:
    """Stack per-region chunks into one [R, cap] batch.

    All regions pad to a common capacity and common string widths so the
    stacked arrays are rectangular; `n_total` (>= len(chunks)) additionally
    pads the region axis with empty lanes so R is divisible by the mesh
    size. Delegates to the chunk layer's `to_stacked_device_batch` — ONE
    stacking implementation serves the batch coprocessor, the mesh tier
    and this entry point (host-side np.stack, one HBM transfer per column).
    """
    cap = capacity or max(1, max(c.num_rows() for c in chunks))
    fts = chunks[0].field_types()
    total = n_total or len(chunks)
    padded = list(chunks) + [Chunk.empty(fts) for _ in range(total - len(chunks))]
    return to_stacked_device_batch(padded, cap)


def run_sharded_partial_agg(dag, stacked: DeviceBatch, mesh: Mesh):
    """Scalar-aggregation pushdown over a region-sharded mesh.

    DAG shape: TableScan [Selection] Aggregation(group_by=(), partial=True).
    Each device vmaps the fused per-region program over its local regions,
    then the partial states merge across the mesh (`merge_packed_states`:
    psum for additive states, pmin/pmax for extremes, all_gather for
    bit/first states) — every device ends with the global partial states.

    The per-region pipeline is the builder's own trace (`exec/builder.py`
    build_program(mesh_lanes=...)), not a second hand-rolled interpreter —
    the duplicated scan/selection/agg walk this module used to carry is
    retired onto that shared seam.

    Returns the flat partial-state columns [(value[1], null[1]), ...].
    """
    from dataclasses import replace as _replace

    from ..distsql.planner import mesh_merge_kind
    from ..exec.builder import build_program
    from ..exec.dag import current_schema_fts

    # this entry point always returns EVERY partial-state column — widen
    # the offsets to the full partial schema (callers pass scan-shaped
    # offsets; the merge plan is positional over the state columns)
    n_state = len(current_schema_fts(dag.executors))
    dag = _replace(dag, output_offsets=tuple(range(n_state)))
    # the scalar merge plan is positional over flat [1]-shaped states — a
    # grouped DAG's per-region group tables are NOT key-aligned across
    # lanes and must fail fast, as this entry point always did. String
    # gather states trip the planner gate statically here (the in-trace
    # merge would raise the same NotImplementedError class).
    last = dag.executors[-1]
    from ..exec.dag import Aggregation as _Agg

    assert isinstance(last, _Agg) and not last.group_by, "sharded scalar agg only"
    if mesh_merge_kind(dag) != "scalar":
        raise NotImplementedError(
            "string-valued gather aggregate (first_row/min/max) over the mesh"
        )
    R = int(stacked.row_valid.shape[0])
    cap = int(stacked.row_valid.shape[1])
    prog = build_program(
        dag, (cap,), mesh_lanes=R, mesh_devices=int(mesh.devices.size),
        mesh_kind="scalar",
    )
    from ..exec import launch

    (merged, _valid, _ex, _ovf, _esc), _, _ = launch.run_program(
        prog.outputs, (stacked,), dag.program_operands(), first_call=True)
    return merged  # host arrays


# --------------------------------------------------------- the merge seam

def partial_merge_plan(aggs) -> list[tuple]:
    """Merge plan per aggregate (the schema in expr/agg.py partial_fts:
    count->[cnt], sum->[sum], avg->[cnt,sum], first_row->[has,val],
    stddev/var->[cnt,sum,sumsq], ...).

    Column entries are ("col", op, unsigned): unsigned BIGINT min/max
    states are raw two's-complement int64 (ops/aggregate.py sign-flip
    trick), so the mesh merge must compare them in the flipped domain too.
    first_row's two state columns merge JOINTLY (value selected by the has
    column) via the ("first_row",) entry consuming both."""
    plan: list[tuple] = []
    for desc in aggs:
        sfts = desc.partial_fts()
        if desc.name in ("count", "sum", "avg", "bit_xor",
                         "stddev_pop", "stddev_samp", "var_pop", "var_samp"):
            # avg states are [count, sum], moment states [count, sum,
            # sumsq] — all additive; bit_xor merge is xor
            op = "sum" if desc.name != "bit_xor" else "xor"
            plan.extend(("col", op, False) for _ in sfts)
        elif desc.name in ("min", "max"):
            plan.extend(("col", desc.name, ft.is_unsigned() and ft.is_int()) for ft in sfts)
        elif desc.name in ("bit_and", "bit_or"):
            plan.extend(("col", "and" if desc.name == "bit_and" else "or", False) for _ in sfts)
        elif desc.name == "first_row":
            plan.append(("first_row",))
        else:
            raise TypeError(f"no mesh merge for aggregate {desc.name!r}")
    return plan


def merge_packed_states(aggs, packed, axis: str = REGION_AXIS) -> list[tuple]:
    """Merge a vmapped partial-agg program's packed outputs across the
    mesh. `packed` is the per-lane output list — one (value[R_local, 1],
    null[R_local, 1]) pair per partial-state column, in `partial_merge_plan`
    order (exactly `exec/builder.py`'s packing for a scalar partial-agg
    DAG). Returns the globally merged [(value[1], null[1]), ...]."""
    plan = partial_merge_plan(aggs)
    merged: list[tuple] = []
    k = 0
    for entry in plan:
        if entry[0] == "first_row":
            has_out, val_out = packed[k], packed[k + 1]
            if len(val_out) != 2 or val_out[0].ndim != 2:
                raise NotImplementedError(
                    "string-valued gather aggregate (first_row/min/max) over the mesh"
                )
            merged.extend(_merge_first_row(
                (has_out[0], has_out[1]), (val_out[0], val_out[1]), axis))
            k += 2
            continue
        _, op, unsigned = entry
        out = packed[k]
        if len(out) != 2 or out[0].ndim != 2:
            raise NotImplementedError(
                "string-valued gather aggregate (first_row/min/max) over the mesh"
            )
        merged.append(_merge_state(op, out[0], out[1], axis, unsigned=unsigned))
        k += 1
    return merged


def _merge_state(op: str, v, nl, axis: str, unsigned: bool = False):
    """Merge one partial-state column across local regions then the mesh.

    v: [R_local, 1] values (NULL lanes zeroed), nl: [R_local, 1] null flags.
    NULL means "no rows seen in this region"; the merged state is NULL only
    if every region's is (ref: aggfuncs partial merge semantics). Sum-like
    states ride psum over ICI (the north-star collective); min/max ride
    pmin/pmax; bit/first states all_gather (tiny) and reduce locally.

    unsigned min/max states hold unsigned values as raw two's-complement
    int64 — compare in the sign-flipped domain (same trick as the kernel).
    """
    allnull = jnp.all(nl, axis=0)
    flip = None
    if unsigned and op in ("min", "max") and jnp.issubdtype(v.dtype, jnp.integer):
        flip = jnp.int64(-0x8000000000000000)
        v = v.astype(jnp.int64) ^ flip
    if op in ("sum", "xor", "or"):
        fill = jnp.zeros((), v.dtype)
    elif op == "and":
        fill = jnp.full((), -1, v.dtype)
    elif op == "min":
        fill = (jnp.full((), jnp.inf, v.dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                else jnp.full((), jnp.iinfo(v.dtype).max, v.dtype))
    elif op == "max":
        fill = (jnp.full((), -jnp.inf, v.dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                else jnp.full((), jnp.iinfo(v.dtype).min, v.dtype))
    else:
        raise AssertionError(op)
    masked = jnp.where(nl, fill, v)

    if op == "sum":
        val = jax.lax.psum(jnp.sum(masked, axis=0), axis)
    elif op == "min":
        val = jax.lax.pmin(jnp.min(masked, axis=0), axis)
    elif op == "max":
        val = jax.lax.pmax(jnp.max(masked, axis=0), axis)
    else:  # xor / or / and: all_gather (tiny) then local bitwise reduce
        red = {"xor": jnp.bitwise_xor, "or": jnp.bitwise_or, "and": jnp.bitwise_and}[op]
        local = red.reduce(masked, axis=0)
        gathered = jax.lax.all_gather(local, axis)  # [D, 1]
        val = red.reduce(gathered, axis=0)
    allnull = jax.lax.pmin(allnull.astype(jnp.int32), axis) > 0
    if flip is not None:
        val = val ^ flip
    if op in ("min", "max"):
        val = jnp.where(allnull, jnp.zeros((), val.dtype), val)
    return val, allnull


def _merge_first_row(has_state, val_state, axis: str):
    """first_row's [has, value] states merge jointly: the first region in
    global region order (device-major — regions were stacked then sharded on
    the leading axis) with has>0 supplies its (value, null) verbatim; NULL
    first values are kept (ref: aggfuncs first_row takes the literal first
    row). Returns the two merged state columns [has, value]."""
    has, _ = has_state
    v, nl = val_state
    ghas = jax.lax.all_gather(has, axis).reshape((-1,) + has.shape[1:])
    gv = jax.lax.all_gather(v, axis).reshape((-1,) + v.shape[1:])
    gn = jax.lax.all_gather(nl, axis).reshape((-1,) + nl.shape[1:])
    present = ghas > 0
    idx = jnp.argmax(present, axis=0)
    any_has = jnp.any(present, axis=0)
    val = jnp.take_along_axis(gv, idx[None], axis=0)[0]
    null = jnp.take_along_axis(gn, idx[None], axis=0)[0]
    val = jnp.where(any_has & ~null, val, jnp.zeros((), v.dtype))
    null = jnp.where(any_has, null, True)
    return [(any_has.astype(jnp.int64), jnp.zeros_like(null)), (val, null)]


def decode_group_mesh_outputs(outs, fetch, out_fts, stats: dict | None = None):
    """Shared host-side decode for the grouped shard_map programs
    (grouped.py / exchange_op.py): flat output tuple [groups before the
    tail, rows after it, (value, null)*, need, overflow]
    (`grouped.tail_phase`) with out_specs P(REGION_AXIS) having already
    concatenated the per-device tables along axis 0, as the launch read
    them (host arrays) with the launch's `fetch`. Returns (chunk, overflow)
    in `out_fts`' layout; `stats`, where given, receives `need` and
    `groups` (final groups before the tail).
    """
    from ..exec import launch
    from ..exec.executor import decode_outputs

    with launch.read_back(fetch) as to_host:
        groups = to_host(outs[0]).reshape(-1)
        valid = to_host(outs[1]).reshape(-1)
        overflow = bool(to_host(outs[-1]).reshape(-1)[0])
        if stats is not None:
            stats["need"] = int(to_host(outs[-2]).reshape(-1)[0])
            stats["groups"] = int(groups.sum())
        flat_out = outs[2:-2]
        packed = []
        for i, _ft in enumerate(out_fts):
            v = to_host(flat_out[2 * i])
            nl = to_host(flat_out[2 * i + 1]).reshape(-1)
            packed.append((v, nl))
        return decode_outputs(packed, valid, out_fts), overflow


def group_mesh_out_spec(n_out_cols: int):
    """out_specs for the grouped shard_map programs' flat output tuple
    (`grouped.tail_phase`): two validity vectors and a (value, null) pair
    a column, sharded; the need hint and the overflow flag, replicated."""
    from jax.sharding import PartitionSpec as P

    return tuple([P(REGION_AXIS)] * (2 + 2 * n_out_cols) + [P(), P()])
