"""On-device exchange operator — ExchangeSender / ExchangeReceiver as mesh
collectives (ref: unistore/cophandler/mpp_exec.go:609-841 exchSenderExec /
exchRecvExec; partition modes :669-719).

The reference's ExchangeSender hash-partitions rows by fnv64 over the
encoded partition keys into per-task tunnels, and ExchangeReceiver merges
the streams. On TPU the tunnels are a single `jax.lax.all_to_all` over the
mesh axis: each device scatters its rows into P send buckets by key hash,
the collective transposes buckets across devices, and every device ends up
owning one hash partition — then local group aggregation (or join
build/probe) runs on owned rows only.

This module is the ONE home of that machinery (ISSUE 18): the scatter ->
all_to_all -> flatten sequence that used to be hand-rolled four times over
(`parallel/exchange.py`, joinmesh's `_exchange_side`, grouped's state and
distinct phases) is `exchange_arrays`; the shuffle-join device program
(`run_exchange_join_agg`) lives here and `parallel/joinmesh.py` wraps it.
The all_to_all is explicit — not sharding-propagated — because the
partition function is data-dependent (hash of key values).

`local_partition_join` is the per-partition join the receivers feed: the
planner-unified key shape routes through the radix-partitioned kernel when
its plan gate passes (including the NON-unique build via the expansion
lift, the ISSUE 13 follow-on), and through the monolithic sort-merge
kernel otherwise — one semantics, strategy-routed at trace time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..expr.compile import CompVal, ExprCompiler, normalize_device_column
from ..ops import apply_selection
from ..ops.keys import sort_key_arrays

# the 1-D mesh axis every exchange collective runs over; canonical HERE so
# the operator has no import-time dependency on parallel/ (parallel/mesh.py
# re-exports it — the wrapper depends on the subsystem, never the reverse)
REGION_AXIS = "region"

FNV_OFFSET = np.int64(-3750763034362895579)  # 0xcbf29ce484222325 as i64; numpy: import-time pure
FNV_PRIME = np.int64(1099511628211)
# murmur3 fmix64 constants (as i64 two's complement). The FNV fold alone is
# NOT enough to partition with: one multiply by an odd prime leaves
# `h mod 2^b` a function of `k mod 2^b` alone, so with a power-of-two
# n_parts the partition id ignores every high bit — derived keys that share
# low bits with the previous stage's key (ckey = oid % 64 after an exchange
# on oid) land 100% of a device's rows in ONE bucket, and all-even keys use
# half the partitions. The xor-shift finalizer avalanches high bits down.
FMIX_C1 = np.int64(np.uint64(0xFF51AFD7ED558CCD).astype(np.int64))
FMIX_C2 = np.int64(np.uint64(0xC4CEB9FE1A85EC53).astype(np.int64))


def hash_partition_ids(key_vals: list[CompVal], n_parts: int) -> jax.Array:
    """Row -> partition id in [0, n_parts) from an FNV-style fold over the
    normalized key words, finished with the murmur3 fmix64 avalanche (NULL
    hashes to partition of its zeroed words — all NULLs land together, as
    the reference's encoded-datum hash does)."""
    h = jnp.broadcast_to(FNV_OFFSET, key_vals[0].null.shape)
    for kv in key_vals:
        for w in sort_key_arrays(kv):
            if jnp.issubdtype(w.dtype, jnp.floating):
                # real keys stay float in sort_key_arrays (TPU x64 emulation
                # can't bitcast f64<->s64); a f32 bitcast is supported and
                # equal doubles hash equal, which is all partitioning needs
                w = jax.lax.bitcast_convert_type(w.astype(jnp.float32), jnp.int32).astype(jnp.int64)
            h = (h ^ w) * FNV_PRIME
    h = (h ^ jax.lax.shift_right_logical(h, 33)) * FMIX_C1
    h = (h ^ jax.lax.shift_right_logical(h, 33)) * FMIX_C2
    h = h ^ jax.lax.shift_right_logical(h, 33)
    # avoid negative mod
    return jnp.abs(h % n_parts).astype(jnp.int32)


def scatter_to_buckets(cols: list[jax.Array], valid: jax.Array, part: jax.Array, n_parts: int, bucket_cap: int):
    """Pack rows into [n_parts, bucket_cap] send buffers by partition id.

    Position within a bucket = rank of the row among same-partition rows
    (prefix count). Returns (bucketed cols, bucket valid, overflow flag).
    """
    n = valid.shape[0]
    part = jnp.where(valid, part, n_parts)  # invalid rows -> ghost bucket
    onehot = part[:, None] == jnp.arange(n_parts + 1)[None, :]  # [n, P+1]
    rank = jnp.cumsum(onehot, axis=0) - 1  # rank within partition
    pos_in_bucket = jnp.take_along_axis(rank, part[:, None], axis=1)[:, 0]
    counts = onehot.sum(axis=0)[:n_parts]
    overflow = jnp.any(counts > bucket_cap)
    flat_pos = part * bucket_cap + jnp.minimum(pos_in_bucket, bucket_cap - 1)
    total = (n_parts + 1) * bucket_cap

    out_valid = jnp.zeros(total, bool).at[flat_pos].set(valid & (pos_in_bucket < bucket_cap))
    out_cols = []
    for c in cols:
        buf = jnp.zeros((total,) + c.shape[1:], c.dtype)
        buf = buf.at[flat_pos].set(c)
        out_cols.append(buf.reshape((n_parts + 1, bucket_cap) + c.shape[1:])[:n_parts])
    return out_cols, out_valid.reshape(n_parts + 1, bucket_cap)[:n_parts], overflow


def exchange_arrays(arrays: list[jax.Array], valid, part, n_parts: int, bucket_cap: int, axis: str = REGION_AXIS):
    """ExchangeSender Hash mode + ExchangeReceiver merge for raw arrays:
    scatter rows into per-destination buckets, `all_to_all` the buckets
    over the mesh axis (dim0 indexes destination partition going in, source
    device coming out — ref: ExchangerTunnel per-task streams), and flatten
    the received [P, cap] tables back to rows. Returns (arrays, valid,
    overflow): every row of this device's hash partition, from all peers."""
    bufs, bvalid, overflow = scatter_to_buckets(arrays, valid, part, n_parts, bucket_cap)
    recv = [jax.lax.all_to_all(b, axis, 0, 0, tiled=False) for b in bufs]
    rvalid = jax.lax.all_to_all(bvalid, axis, 0, 0, tiled=False)
    flat = [r.reshape((-1,) + r.shape[2:]) for r in recv]
    return flat, rvalid.reshape(-1), overflow


def broadcast_exchange(mesh_axis: str, cols: list, valid):
    """Broadcast mode (ref: mpp_exec.go:669 Broadcast partition type, the
    TiFlash broadcast-join operand path): every device receives EVERY row.
    Returns ([P*n]-shaped cols, valid) identical on all devices — one
    all_gather over ICI per column."""
    out_cols = []
    for c in cols:
        g = jax.lax.all_gather(c, mesh_axis, axis=0, tiled=False)  # [P, n, ...]
        out_cols.append(g.reshape((-1,) + c.shape[1:]))
    gv = jax.lax.all_gather(valid, mesh_axis, axis=0, tiled=False).reshape(-1)
    return out_cols, gv


def passthrough_exchange(mesh_axis: str, cols: list, valid, target: int = 0):
    """PassThrough mode (ref: mpp_exec.go:669-719 PassThrough partition
    type — the root-gather: every task streams all rows to the single
    collector). All devices' rows land on `target`; other devices keep the
    buffers (SPMD static shapes) with all-False validity."""
    out_cols, gv = broadcast_exchange(mesh_axis, cols, valid)
    me = jax.lax.axis_index(mesh_axis)
    gv = gv & (me == target)
    return out_cols, gv


def exchange_group_aggregate(mesh_axis: str, key_vals, agg_fn, cols, valid, n_parts: int, bucket_cap: int):
    """Inside shard_map: hash-exchange rows so each device owns one hash
    partition, then run `agg_fn(owned_cols, owned_valid)` locally.

    agg_fn receives rows of shape [n_parts * bucket_cap] (all rows of this
    device's partition gathered from every peer).
    """
    part = hash_partition_ids(key_vals, n_parts)
    flat_cols, flat_valid, overflow = exchange_arrays(cols, valid, part, n_parts, bucket_cap, axis=mesh_axis)
    overflow = jax.lax.pmax(overflow.astype(jnp.int32), mesh_axis) > 0
    return agg_fn(flat_cols, flat_valid), overflow


def exchange_compvals(cvals: list[CompVal], valid, part, n_parts: int, bucket_cap: int, axis: str = REGION_AXIS):
    """`exchange_arrays` over typed columns: each CompVal rides the wire as
    its (value, null) array pair and is reassembled on the receiver with
    its FieldType intact."""
    flat = [a for c in cvals for a in (c.value, c.null)]
    flat_r, rvalid, ovf = exchange_arrays(flat, valid, part, n_parts, bucket_cap, axis=axis)
    out = [
        CompVal(flat_r[2 * i], flat_r[2 * i + 1].astype(bool), c.ft)
        for i, c in enumerate(cvals)
    ]
    return out, rvalid, ovf


def gather_compvals(cols: list[CompVal], idx) -> list[CompVal]:
    out = []
    for c in cols:
        if c.value.ndim == 2:
            out.append(CompVal(c.value[idx, :], c.null[idx], c.ft))
        else:
            out.append(CompVal(c.value[idx], c.null[idx], c.ft))
    return out


def local_partition_join(build_keys, probe_keys, build_valid, probe_valid,
                         out_capacity: int, join_type: str, build_unique: bool):
    """The per-partition join above the receivers (ref: mpp_exec.go:844
    joinExec). Strategy-routed at TRACE time on static shapes: the
    radix-partitioned kernel when its plan gate passes on a single-word
    int-class key — including the NON-unique build, which rides the
    expansion lift the exchange unlocked (ops/radix_join.py) — and the
    monolithic sort-merge kernel everywhere else. Identical JoinResult
    contract either way, so the caller never knows which ran."""
    from ..ops.join import _key_matrix, hash_join
    from ..ops.radix_join import radix_hash_join, radix_plan

    nb = int(build_valid.shape[0])
    np_ = int(probe_valid.shape[0])
    plan = radix_plan(nb, np_, out_capacity)
    if plan is not None and len(build_keys) == 1 and len(probe_keys) == 1:
        bw, _bu = _key_matrix(build_keys, build_valid)
        pw, _pu = _key_matrix(probe_keys, probe_valid)
        if (len(bw) == 1 and len(pw) == 1
                and not jnp.issubdtype(bw[0].dtype, jnp.floating)
                and not jnp.issubdtype(pw[0].dtype, jnp.floating)):
            res, _escapes = radix_hash_join(
                build_keys, probe_keys, build_valid, probe_valid,
                join_type, out_capacity, plan,
                build_unique=build_unique, out_capacity=out_capacity,
            )
            return res
    return hash_join(
        build_keys, probe_keys, build_valid, probe_valid,
        out_capacity=out_capacity, join_type=join_type,
        build_unique=build_unique,
    )


def exchange_join_program(dag, mesh, group_capacity: int = 1024, scale: int = 1):
    """Build (don't run) the shuffle-join shard_map program for an eligible
    chain DAG: `fn(stacked_probe, *stacked_builds) -> flat group outputs`.
    Split from `run_exchange_join_agg` so the jax-audit catalog can trace
    the exchange-join shape through the jaxpr checks without launching."""
    from ..exec.dag import operand_lanes
    from ..parallel.grouped import _flatten_local, agg_exchange_phases, tail_phase
    from .fragment import split_join_dag, split_tail

    # what is traced is the plan's shape (exec/builder.py build_program):
    # the parameterisable constants of the DAG that happens to build the
    # program follow the batches as operands, like any later DAG's
    dag, _key, operands = dag.parameterized()
    lanes = operand_lanes(operands)
    dag, tail = split_tail(dag)
    parts = split_join_dag(dag)
    assert parts is not None, "not a shuffle-join DAG shape"
    probe_scan, pre_sels, stages, agg = parts
    pfts = [c.ft for c in probe_scan.columns]
    n_parts = mesh.devices.size
    n_builds = len(stages)

    def device_fn(lp, *rest):
        lbs, params = rest[:n_builds], dict(zip(lanes, rest[n_builds:]))
        pcols, pvalid = _flatten_local(lp)
        pc = [normalize_device_column(c) for c in pcols]
        for ex in pre_sels:
            conds = ExprCompiler(pfts, params).run(list(ex.conditions), pc)
            pvalid = apply_selection(pvalid, conds)
        # drop raw string bytes: only packed words cross the exchange
        pc = [CompVal(c.value, c.null, c.ft) for c in pc]
        schema = list(pfts)
        valid = pvalid
        cols = pc
        extra = jnp.bool_(False)
        # expected VALID rows per device (static): post-exchange each device
        # owns one hash partition ~ total/n, and total stacked rows are
        # n * lane_rows — so the fair share IS the lane size. Capacities
        # derive from this estimate, NOT from the previous stage's padded
        # slot count: slot-derived caps compound `2*scale` per stage
        # (scale^2 across a chain: 500K-slot exchanges for a 16K-row
        # table on eight devices). Skew past the 2x headroom is the
        # ladder's job, and `scale` grows est linearly, never quadratically.
        est = valid.shape[0]

        for (join, post_sels), lb in zip(stages, lbs):
            bfts = [c.ft for c in join.build[0].columns]
            bcols, bvalid = _flatten_local(lb)
            bc = [normalize_device_column(c) for c in bcols]
            for ex in join.build[1:]:
                conds = ExprCompiler(bfts, params).run(list(ex.conditions), bc)
                bvalid = apply_selection(bvalid, conds)
            bc = [CompVal(c.value, c.null, c.ft) for c in bc]

            # hash-partition both sides by THIS stage's join key
            pkeys = ExprCompiler(schema, params).run(list(join.probe_keys), cols)
            bkeys = ExprCompiler(bfts, params).run(list(join.build_keys), bc)
            # 2.5x the fair share: hash partitioning is balanced per KEY,
            # not per row — a few dozen fat keys per device routinely put
            # one partition ~2.5x over the row mean, and a whole ladder
            # rung costs more than the 25% slack
            pcap = max(64, 5 * scale * est // (2 * n_parts))
            bcap_ = max(64, 5 * scale * bvalid.shape[0] // (2 * n_parts))
            pp = hash_partition_ids(pkeys, n_parts)
            bp = hash_partition_ids(bkeys, n_parts)
            pc2, pvalid2, povf = exchange_compvals(cols, valid, pp, n_parts, pcap)
            bc2, bvalid2, bovf = exchange_compvals(bc, bvalid, bp, n_parts, bcap_)

            # local join on the owned partition (ref: joinExec above receivers)
            pkeys2 = ExprCompiler(schema, params).run(list(join.probe_keys), pc2)
            bkeys2 = ExprCompiler(bfts, params).run(list(join.build_keys), bc2)
            if join.join_type in ("semi", "anti"):
                out_cap = pvalid2.shape[0]  # probe-shaped output
            else:
                if not join.build_unique:
                    est = 4 * est  # duplicate-build fan-out headroom
                out_cap = max(128, 2 * scale * est)
            res = local_partition_join(
                bkeys2, pkeys2, bvalid2, pvalid2,
                out_capacity=out_cap,
                join_type=join.join_type,
                build_unique=join.build_unique,
            )
            extra = extra | povf | bovf | res.overflow
            if join.join_type in ("semi", "anti"):
                cols = pc2
                valid = res.out_valid
            else:
                nb = bvalid2.shape[0]
                p_g = pc2 if res.probe_identity else gather_compvals(pc2, res.probe_idx)
                b_g = gather_compvals(bc2, jnp.clip(res.build_idx, 0, nb - 1))
                b_g = [CompVal(c.value, c.null | res.build_null, c.ft) for c in b_g]
                cols = p_g + b_g
                valid = res.out_valid
                schema = schema + (
                    [f.clone_nullable() for f in bfts]
                    if join.join_type == "left_outer" else bfts
                )
            for ex in post_sels:
                conds = ExprCompiler(schema, params).run(list(ex.conditions), cols)
                valid = apply_selection(valid, conds)

        # the state-exchange bucket cap is data-sized like the join
        # exchanges (distinct groups <= rows; gc-sized buckets made the agg
        # phase 8x the whole join's work at the upper ladder rungs)
        outs = agg_exchange_phases(
            agg, schema, cols, valid, n_parts, group_capacity,
            max(64, 2 * scale * est // n_parts), extra_overflow=extra, params=params,
        )
        return tail_phase(outs, agg, tail, params)

    from jax.sharding import PartitionSpec as P

    from ..parallel.grouped import tail_fts
    from ..parallel.mesh import group_mesh_out_spec

    def wrap(stacked_probe, *rest):
        # the batches shard their region axis; the operands replicate
        specs = tuple(jax.tree.map(lambda _: P(REGION_AXIS), b) for b in (stacked_probe, *rest[:n_builds]))
        fn = jax.shard_map(device_fn, mesh=mesh, in_specs=specs + (P(),) * len(lanes),
                           out_specs=group_mesh_out_spec(len(tail_fts(agg, tail))), check_vma=False)
        return fn(stacked_probe, *rest)

    return wrap


def run_exchange_program(name: str, dag, mesh, build, cap_key: tuple, args: tuple, programs=None):
    """`build() -> fn(*args, *operands)`, jitted under `name`, kept in
    `programs` (a `ProgramCache`: the store's, else the process's default)
    under what it depends on — the DAG's `program_key()`, the mesh's
    devices, the capacities — and called with `args` and the DAG's
    `program_operands()` through the launch boundary: a fresh literal
    calls the program that is there, and building one counts as every
    other program's does (`PROGRAM_COMPILES`, `exec.program`).  A fresh
    `jax.jit(closure)` per statement would re-trace the whole shard_map
    program every time.  The host decodes every output, so all of them
    ride the program's one buffer.  Returns (outputs as host arrays, the
    launch's `Fetch`)."""
    from ..exec import launch
    from ..exec.executor import DEFAULT_PROGRAM_CACHE
    from ..ops.dense_pallas import pallas_mode

    def make():
        body = build()
        body.__name__ = body.__qualname__ = name
        return launch.HostOutputs(body)

    cache = DEFAULT_PROGRAM_CACHE if programs is None else programs
    key = (name, dag.program_key(), tuple(int(d.id) for d in mesh.devices.flat), *cap_key, pallas_mode())
    outputs, hit, _ = cache.built(key, make)
    outs, fetch, _ = launch.run_program(outputs, args, dag.program_operands(), first_call=not hit)
    return outs, fetch


def run_exchange_join_agg(
    dag,
    stacked_probe,
    stacked_builds: list,
    mesh,
    group_capacity: int = 1024,
    scale: int = 1,
    programs=None,
    stats: dict | None = None,
):
    """Execute scan [sel] (JOIN(scan [sel]) [sel])+ GROUP BY [tail] over the
    mesh as ONE shard_map program; returns (chunk, overflow flag). Output
    layout matches the single-chip executor: [agg results..., group
    keys...], or the tail's schema (`parallel/grouped.py`
    `run_sharded_grouped_agg`, which fills `stats` alike).
    Multi-join chains (TPC-H Q3) re-exchange the widened probe schema at
    every stage by that stage's join key — the per-fragment dataflow
    `mpp/fragment.py` plans is exactly these phases.

    Exchange buckets are sized ~2x the per-device fair share (total/n) so
    per-device post-exchange work stays ~1/n of the table — the point of
    the repartition; `scale` (grown by the caller's overflow retry)
    multiplies every data-dependent capacity: exchange buckets for skewed
    keys and the join out-capacity for fan-out > 1."""
    from ..parallel.grouped import tail_fts
    from ..parallel.mesh import decode_group_mesh_outputs
    from .fragment import split_join_dag, split_tail

    if not isinstance(stacked_builds, (list, tuple)):
        stacked_builds = [stacked_builds]
    head, tail = split_tail(dag)
    n_stages = len(split_join_dag(head)[2])
    assert len(stacked_builds) == n_stages, "one build batch per join stage"
    agg = head.executors[-1]
    outs, fetch = run_exchange_program(
        "mpp_exchange_join_agg", dag, mesh,
        lambda: exchange_join_program(dag, mesh, group_capacity=group_capacity, scale=scale),
        (group_capacity, scale), (stacked_probe, *stacked_builds), programs)
    # decode via the shared seam (parallel/mesh.py) — same layout as grouped
    return decode_group_mesh_outputs(outs, fetch, tail_fts(agg, tail), stats)
