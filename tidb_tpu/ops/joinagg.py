"""Fused sort-merge join + stream aggregation — the TPC-H Q3 shape.

When a unique-build inner join feeds a GROUP BY on exactly the probe-side
join key, the join's merge sort already clusters rows by the group key, so
ONE variadic sort (build and probe key words interleaved, agg arguments
riding as payload operands) performs the probe AND the grouping. The
general pipeline pays three more full-size sorts on top of that one — the
inverse permutation back to probe order, the aggregation's hash-cluster
sort, and the segment-boundary construction — and this kernel skips all of
them: a stream-agg boundary scan runs directly on the merge order.

On TPU the sort IS the unit of cost for join/group plans (every other pass
is a cumsum-class scan), so sharing one sort between the two operators is
the whole win — the analog of the reference handing hash-join output
straight to a stream aggregate when orders match (ref:
pkg/executor/join/hash_join_v2.go build/probe,
pkg/executor/aggregate/agg_stream_executor.go sorted-input contract).

Matching mirrors ops/join.py's unique-build inner-join semantics exactly:
NULL keys never match, a build fan-out > 1 raises the join-overflow flag
(the driver retries on the general kernel), and group capacity overflow
raises the group flag. Output group order is the oracle's first-encounter
order (earliest contributing probe row), recovered by riding the original
probe index through the sort.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..expr.compile import CompVal
from .aggregate import GatherState, _group_aggregate_stream
from .join import _key_matrix
from .seg import I64_MAX

# aggregate names the stream kernel evaluates without raw-byte payloads or
# the DISTINCT hash machinery (ops/aggregate.py _agg_states_raw coverage)
FUSABLE_AGGS = frozenset({
    "count", "sum", "avg", "min", "max", "first_row",
    "bit_and", "bit_or", "bit_xor",
    "stddev_pop", "stddev_samp", "var_pop", "var_samp",
})


def join_stream_agg(
    build_keys: list[CompVal],
    probe_keys: list[CompVal],
    build_valid,
    probe_valid,
    aggs: list,
    group_capacity: int,
):
    """One-sort unique-build inner join + GROUP BY probe key.

    aggs: list of (AggDesc, [probe-row-order arg CompVals]); every arg must
    be single-word (ndim 1, no raw bytes) — the caller checks eligibility.
    Returns (GroupAggResult, sorted_arg_lists, group_out CompVal,
    join_overflow, join_rows); res.group_rep indexes the SORTED row space,
    aligned with sorted_arg_lists and group_out; join_rows is the joined
    row count for the exec summaries.
    """
    bw_l, b_usable = _key_matrix(build_keys, build_valid)
    pw_l, p_usable = _key_matrix(probe_keys, probe_valid)
    assert len(bw_l) == 1 and len(pw_l) == 1, "joinagg needs single-word keys"
    bw, pw = bw_l[0], pw_l[0]
    nb, np_ = bw.shape[0], pw.shape[0]
    n = nb + np_
    top = jnp.inf if jnp.issubdtype(bw.dtype, jnp.floating) else I64_MAX
    vals = jnp.concatenate([
        jnp.where(b_usable, bw, top), jnp.where(p_usable, pw, top),
    ])
    # sort key 2: build rows first within an equal-key run, so a probe row's
    # cumulative hay count already includes its whole run; lax.sort is
    # stable, so probe rows keep original ascending order inside a run
    side = jnp.concatenate([jnp.zeros(nb, jnp.int8), jnp.ones(np_, jnp.int8)])

    payload: list = []
    slot_of: dict = {}

    def carry(hay_fill, arr) -> int:
        key = (id(arr), repr(hay_fill))
        if key not in slot_of:
            slot_of[key] = len(payload)
            payload.append(jnp.concatenate([
                jnp.full((nb,), hay_fill, arr.dtype), arr,
            ]))
        return slot_of[key]

    # original probe index (first-encounter output order + group_rep remap)
    iota_slot = len(payload)
    payload.append(jnp.concatenate([
        jnp.full(nb, n, jnp.int32), jnp.arange(np_, dtype=jnp.int32),
    ]))
    # group-by output value = the probe key's original value lane
    gkey_slot = carry(0, probe_keys[0].value)

    bool_arrs: list = [jnp.concatenate([b_usable, p_usable])]
    bool_ix: dict = {}

    def carry_bool(hay_fill: bool, arr) -> int:
        key = (id(arr), hay_fill)
        if key not in bool_ix:
            bool_ix[key] = len(bool_arrs)
            bool_arrs.append(jnp.concatenate([
                jnp.full(nb, hay_fill, bool), arr,
            ]))
        return bool_ix[key]

    plans = []  # per agg: [(value_slot, null_bit)] per arg
    for desc, avs in aggs:
        slots = []
        for a in avs:
            slots.append((carry(0, a.value), carry_bool(True, a.null)))
        plans.append(slots)

    nwords = []
    for w0 in range(0, len(bool_arrs), 8):
        grp = bool_arrs[w0 : w0 + 8]
        word = grp[0].astype(jnp.uint8)
        for k, a in enumerate(grp[1:], start=1):
            word = word | (a.astype(jnp.uint8) << k)
        nwords.append(word)

    sorted_ops = jax.lax.sort(tuple([vals, side] + payload + nwords), num_keys=2)
    sv, ss = sorted_ops[0], sorted_ops[1]
    pay_s = list(sorted_ops[2 : 2 + len(payload)])
    nw_s = list(sorted_ops[2 + len(payload) :])
    usable_s = ((nw_s[0] >> 0) & 1).astype(bool)
    is_hay = ss == 0
    hay_u = is_hay & usable_s

    one = jnp.ones(1, bool)
    diff = jnp.concatenate([one, sv[1:] != sv[:-1]])
    hcnt = jnp.cumsum(hay_u.astype(jnp.int32))
    # usable-hay count strictly before my run (run-start propagation; the
    # marked values are nondecreasing, so a forward cummax broadcasts each
    # run head's value across its run — the merge_lo_hi trick)
    base = jax.lax.cummax(jnp.where(diff, hcnt - hay_u, jnp.int32(-1)))
    matched = (hcnt - base) > 0
    # run's total usable hay: hcnt at the run END, propagated backward
    # (ends carry nondecreasing hcnt, so reverse cummin finds MY run's end)
    emark = jnp.concatenate([diff[1:], one])
    endv = jax.lax.cummin(
        jnp.where(emark, hcnt, jnp.iinfo(jnp.int32).max), reverse=True
    )
    run_hay = endv - base
    contrib = (~is_hay) & usable_s & matched
    # unique-build contract: any probe matching a >1-row build run
    join_overflow = jnp.any((run_hay > 1) & contrib)

    def resort(a: CompVal, slots) -> CompVal:
        vslot, nbit = slots
        null = ((nw_s[nbit // 8] >> (nbit % 8)) & 1).astype(bool)
        return CompVal(pay_s[vslot], null, a.ft)

    key_ft = probe_keys[0].ft
    sorted_aggs = [
        (desc, [resort(a, sl) for a, sl in zip(avs, plan)])
        for (desc, avs), plan in zip(aggs, plans)
    ]
    res = _group_aggregate_stream(
        [CompVal(sv, jnp.zeros(n, bool), key_ft)],
        sorted_aggs, contrib, group_capacity, merge=False, compact=False,
    )

    # compact=False: res.group_valid is raw has-flags in key order. ONE
    # argsort on the earliest ORIGINAL probe index (ridden through the
    # sort) both compacts contributing groups to the front and restores
    # the oracle's first-encounter output order.
    orig_s = pay_s[iota_slot]
    gc = res.group_rep.shape[0]
    orig_first = jnp.where(
        res.group_valid, orig_s[jnp.clip(res.group_rep, 0, n - 1)], jnp.int32(n)
    )
    order = jnp.argsort(orig_first)
    res.group_rep = res.group_rep[order]
    gids = jnp.arange(gc, dtype=jnp.int32)
    res.group_valid = gids < res.n_groups
    states2 = []
    for st in res.states:
        if isinstance(st, GatherState):
            states2.append(GatherState(st.idx[order], st.has[order]))
        else:
            states2.append([(v[order], nl[order]) for v, nl in st])
    res.states = states2

    group_out = CompVal(pay_s[gkey_slot], jnp.zeros(n, bool), key_ft)
    join_rows = contrib.sum().astype(jnp.int64)
    return res, sorted_aggs, group_out, join_overflow, join_rows


# --------------------------------------------------------------------------
# packed-key fast path: bounded-range int keys, sum/count/avg only
# --------------------------------------------------------------------------
#
# Measured v5e floors (2026-07-31, not repeated since): a 2-operand int32
# lax.sort costs ~6ms at 4M rows while adding ONE int64 operand takes it
# to ~16ms and a 3rd int32 operand to ~17.5ms; every scan op has a ~2-3ms
# floor; random gathers are ~16ns/row and scatter-add ~100ns/row
# (useless). The packed path is shaped by those numbers: ONE int32-only
# sort (key+side packed in one word, each agg argument as a SINGLE int32
# lane), match/boundary logic that is pure elementwise neighbor algebra,
# and per-group extents from cumsum + reverse-cummin pairs whose addends
# are statically biased by +2^31 (int32 lanes make the monotonicity
# precondition free — no runtime shift/bound reduce at all, the [2A+1, N]
# min-reduce of the old int64 variant is gone). Outputs live at
# run-boundary positions of the sorted [nb+np] space under a validity
# mask — no group capacity exists, so the overflow-retry ladder never
# fires for group count.
#
# Values outside int32 raise the join-overflow flag and the driver lands
# on the general sort kernel — the same contract key ranges over 2^30
# always had (an opportunistic fast path, never a semantics change).

_PACKED_AGGS = frozenset({"sum", "count", "avg"})
_PK_RANGE = 1 << 30  # packed (key - kmin) must fit 30 bits (plus side bit)
# unusable-row sentinels: above every packed key; hay (even) and probe
# (odd, = _PIN_HAY|1) pins keep is_hay = ~(pk&1) true even for pins
_PIN_HAY = np.int32((1 << 31) - 4)  # numpy: import-time pure (vet: jit-purity)
_PIN_PROBE = np.int32((1 << 31) - 3)
I32_SHIFT = 1 << 31  # static non-negativity bias per addend (plain int:
# a module-level jnp expression would leak a tracer when this module is
# first imported inside a jit trace — the builder imports it lazily)


def _pack_keys(both, ok, side):
    """key << 1 | side as int32; unusable rows pin above all real keys.
    Returns (pk, bad_lane). Keys are packed at their ABSOLUTE value (no
    min-rebase): the old rebasing min-reduce sat on the critical path
    BEFORE the sort (a ~3ms serial dependency on the v5e, 2026-07-31), while
    the |key| < 2^30-2 width check is pure elementwise — out-of-range
    usable keys pin AND mark the bad lane, which the caller folds into
    its one batched overflow any() (-> the general-kernel retry, exactly
    as rebased range overflow always did)."""
    k32 = both.astype(jnp.int32)
    # range check in int64: jnp.abs(k32) wraps for INT32_MIN (abs returns
    # INT32_MIN itself, which passes < 2^30-2), so key -2^31 would pack to
    # pk 0 and silently join as phantom key 0 (ADVICE r5 high). `both` is
    # already int64 — |key| in that domain is exact for every int32 value.
    in_range = (both == k32.astype(jnp.int64)) & (jnp.abs(both) < (_PK_RANGE - 2))
    usable = ok & in_range
    pk = jnp.where(
        usable,
        (k32 << 1) | side,
        jnp.where(side == 0, _PIN_HAY, _PIN_PROBE),
    )
    return pk, ok & ~in_range


def membership_chain(outer_key, outer_ok, inner_key, inner_ok, payload):
    """Unique-build membership join whose OUTPUT ORDER is free.

    Outer rows (e.g. orders) probe inner rows (e.g. customers) on an int
    key; returns (payload_out, ok_out, overflow) of length n_inner+n_outer
    where ok_out marks outer rows that matched a usable inner row — in
    inner-key sort order, which packed_join_groupsum accepts as-is, so NO
    inverse permutation sort is ever paid. payload: int64 per-outer-row
    value carried through (the next join's key); inner slots come back
    with ok_out False. Payloads outside int32 overflow (-> general
    kernel), keeping the sort at TWO int32 operands."""
    no, nc = outer_key.shape[0], inner_key.shape[0]
    both = jnp.concatenate([inner_key.astype(jnp.int64), outer_key.astype(jnp.int64)])
    ok = jnp.concatenate([inner_ok, outer_ok])
    side = jnp.concatenate([jnp.zeros(nc, jnp.int32), jnp.ones(no, jnp.int32)])
    pk, kbad = _pack_keys(both, ok, side)
    pay32 = payload.astype(jnp.int32)
    wbad = (outer_ok & (payload.astype(jnp.int64) != pay32.astype(jnp.int64))) | kbad[nc:]
    wbad = jnp.concatenate([kbad[:nc], wbad])
    pay = jnp.concatenate([jnp.zeros(nc, jnp.int32), pay32])
    spk, spay = jax.lax.sort((pk, pay), num_keys=1)

    from .dense_pallas import pallas_mode

    mode = pallas_mode()
    if mode:
        from .joinscan import membership_segscan

        ok_out, overflow = membership_segscan(
            spk, wbad, interpret=(mode == "interpret")
        )
        return spay.astype(jnp.int64), ok_out, overflow
    is_inner = (spk & 1) == 0
    is_real = spk < _PIN_HAY
    # sentinel below every real pk (|key| < 2^30-2 keeps pk > INT32_MIN+4;
    # -2 collided with real key -1 under no-rebase packing)
    prev_pk = jnp.concatenate([jnp.full(1, -(2**31), jnp.int32), spk[:-1]])
    # duplicate usable inner keys (adjacent equal pk on the inner side) and
    # payload width, batched into ONE any() (reduce floors — see below)
    overflow = jnp.any(jnp.stack([
        is_inner & is_real & (spk == prev_pk),
        wbad,
    ]))
    keydiff = (spk | jnp.int32(1)) != (prev_pk | jnp.int32(1))
    # run-head flag ("head is a usable inner row") packed into the LSB of
    # a strictly increasing head marker, so a forward cummax broadcasts
    # THIS run's head flag without scans ever crossing runs
    n = no + nc
    iota = jnp.arange(n, dtype=jnp.int32)
    marker = jnp.where(
        keydiff,
        iota * 2 + (is_inner & is_real).astype(jnp.int32),
        jnp.int32(-1),
    )
    head = jax.lax.cummax(marker)
    ok_out = (~is_inner) & is_real & ((head & 1) == 1)
    return spay.astype(jnp.int64), ok_out, overflow


def packed_join_groupsum(hay_key, hay_ok, probe_key, probe_ok, aggs):
    """Unique-build inner join + GROUP BY probe key (int class), aggregates
    restricted to sum/count/avg over int/decimal args that fit int32.

    aggs: [(AggDesc, [arg CompVals in probe row order])]. Returns
    (states per agg, group_valid, key_out CompVal, overflow, join_rows);
    everything is in the sorted [nb+np] row space: group results live at
    each group's first probe row, group_valid masks exactly those rows.
    overflow (-> driver's join-overflow retry, landing on the general
    kernel) fires on: key range over 2^30, duplicate usable hay keys
    (unique-build violation), or an agg argument outside int32."""
    nb, np_ = hay_key.shape[0], probe_key.value.shape[0]
    n = nb + np_
    both = jnp.concatenate([hay_key.astype(jnp.int64), probe_key.value.astype(jnp.int64)])
    ok = jnp.concatenate([hay_ok, probe_ok])
    side = jnp.concatenate([jnp.zeros(nb, jnp.int32), jnp.ones(np_, jnp.int32)])
    pk, kbad = _pack_keys(both, ok, side)

    # one int32 sort: packed key + ONE int32 lane per distinct agg argument
    # (nulls pre-masked to 0 so only COUNT needs the null-bit word).
    # NOT NULL args (FieldType flag) skip the null machinery entirely.
    from ..types import Flag

    lanes: list = []
    combo_of: dict = {}
    nullbit_of: dict = {}
    nbits: list = []
    width_bad = jnp.zeros(np_, bool)  # batched into the ONE post-sort reduce
    for desc, avs in aggs:
        for a in avs:
            key = (id(a.value), id(a.null))
            if key not in combo_of:
                combo_of[key] = len(lanes)
                v32 = a.value.astype(jnp.int32)
                width_bad = width_bad | (
                    probe_ok & ~a.null
                    & (a.value.astype(jnp.int64) != v32.astype(jnp.int64))
                )
                vm = jnp.where(a.null, jnp.int32(0), v32)
                lanes.append(jnp.concatenate([jnp.zeros(nb, jnp.int32), vm]))
            if bool(a.ft.flag & Flag.NotNull):
                nullbit_of[id(a.null)] = -1  # alias of the contrib mask
            elif id(a.null) not in nullbit_of:
                nullbit_of[id(a.null)] = len(nbits)
                nbits.append(jnp.concatenate([jnp.ones(nb, bool), a.null]))
    nword = jnp.zeros(n, jnp.uint8)
    for k, b in enumerate(nbits):
        nword = nword | (b.astype(jnp.uint8) << k)
    ops = [pk] + lanes + ([nword] if nbits else [])
    sorted_ops = jax.lax.sort(tuple(ops), num_keys=1)
    spk = sorted_ops[0]
    lanes_s = list(sorted_ops[1 : 1 + len(lanes)])
    nw_s = sorted_ops[-1] if nbits else None

    from .dense_pallas import pallas_mode

    mode = pallas_mode()
    if mode and len(lanes) <= 2:
        # TPU fast path: ONE Pallas sweep replaces every post-sort scan
        # and the overflow reduce (ops/joinscan.py)
        from .joinscan import postsort_segscan

        lane_keys = list(combo_of)
        nn_bits = [nullbit_of[k[1]] for k in lane_keys]
        bad_all = kbad | jnp.concatenate([jnp.zeros(nb, bool), width_bad])
        gv, cnt, key32, sums, nns, ovf, _jr = postsort_segscan(
            spk, lanes_s, bad_all, nw_s=nw_s, nn_bits=nn_bits,
            interpret=(mode == "interpret"),
        )
        by_combo = {k: (sums[i], nns[i]) for i, k in enumerate(lane_keys)}
        zeros = jnp.zeros(n, bool)
        states = []
        for desc, avs in aggs:
            if desc.name == "count":
                if avs:
                    _, nn = by_combo[(id(avs[0].value), id(avs[0].null))]
                    states.append([(nn, zeros)])
                else:
                    states.append([(cnt, zeros)])
                continue
            a = avs[0]
            s, nn = by_combo[(id(a.value), id(a.null))]
            empty = nn == 0
            if desc.name == "sum":
                states.append([(s, empty)])
            else:  # avg: [count, sum]
                states.append([(nn, zeros), (s, empty)])
        key_out = CompVal(
            jnp.where(gv, (key32 >> 1).astype(jnp.int64), jnp.int64(0)),
            zeros, probe_key.ft,
        )
        return states, gv, key_out, ovf, cnt

    is_hay = (spk & 1) == 0
    is_real = spk < _PIN_HAY
    # sentinel below every real pk (|key| < 2^30-2 keeps pk > INT32_MIN+4;
    # -2 collided with real key -1 under no-rebase packing)
    prev_pk = jnp.concatenate([jnp.full(1, -(2**31), jnp.int32), spk[:-1]])
    dup_hay = is_hay & is_real & (spk == prev_pk)
    # ONE batched any() for every per-row overflow condition (each
    # standalone reduce costs a ~1.5-3ms dispatch floor on this platform)
    overflow = jnp.any(
        jnp.stack([dup_hay, kbad | jnp.concatenate([jnp.zeros(nb, bool), width_bad])])
    )
    keydiff = (spk | jnp.int32(1)) != (prev_pk | jnp.int32(1))
    # first probe row of its key run (prev is hay, or a different key);
    # matched iff prev row is the hay of MY key - all neighbor algebra
    pbnd = (~is_hay) & is_real & (keydiff | ((prev_pk & 1) == 0))
    matched = pbnd & (prev_pk == spk - 1)
    emark = jnp.concatenate([keydiff[1:], jnp.ones(1, bool)])

    # run extents: the run end POSITION comes from one int32 reverse
    # cummin and positions give the contributing count directly
    iota = jnp.arange(n, dtype=jnp.int32)
    end_pos = jax.lax.cummin(
        jnp.where(emark, iota, jnp.int32(n)), reverse=True
    )
    extent_cnt = (end_pos - iota + 1).astype(jnp.int64)  # rows self..run end
    big = jnp.int64(0x7FFFFFFFFFFFFFFF)

    def _extent(addends):
        """Sum of `addends` (int64, non-negative) over [self..run end]."""
        c = jnp.cumsum(addends)
        ev = jax.lax.cummin(jnp.where(emark, c, big), reverse=True)
        return ev - (c - addends)

    combo_sum: dict = {}
    combo_nn: dict = {}
    for key, li in combo_of.items():
        shifted = lanes_s[li].astype(jnp.int64) + I32_SHIFT
        # every row in the extent carried (vm + 2^31), null rows as 0+2^31
        combo_sum[key] = _extent(shifted) - extent_cnt * I32_SHIFT
    for desc, avs in aggs:
        for a in avs:
            nb_ = nullbit_of[id(a.null)]
            key = (id(a.value), id(a.null))
            if key in combo_nn:
                continue
            if nb_ < 0:
                combo_nn[key] = extent_cnt
            else:
                nn = (((nw_s >> nb_) & 1) == 0).astype(jnp.int64)
                combo_nn[key] = _extent(nn)

    group_valid = pbnd & matched
    zeros = jnp.zeros(n, bool)
    states = []
    for desc, avs in aggs:
        if desc.name == "count":
            if avs:
                cnt = combo_nn[(id(avs[0].value), id(avs[0].null))]
            else:
                cnt = extent_cnt
            states.append([(cnt, zeros)])
            continue
        a = avs[0]
        key = (id(a.value), id(a.null))
        s = combo_sum[key]
        cnt_nn = combo_nn[key]
        empty = cnt_nn == 0
        if desc.name == "sum":
            states.append([(s, empty)])
        else:  # avg: [count, sum] (expr/agg.py partial schema)
            states.append([(cnt_nn, zeros), (s, empty)])

    key_out = CompVal(
        jnp.where(is_real, (spk >> 1).astype(jnp.int64), jnp.int64(0)),
        zeros, probe_key.ft,
    )
    return states, group_valid, key_out, overflow, extent_cnt
