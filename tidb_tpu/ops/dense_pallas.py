"""One-pass Pallas small-G aggregation — the TPC-H Q1 shape.

The XLA dense kernel (aggregate.py _group_aggregate_dense) materializes a
stack of [N, G] intermediates through HBM (the gid compare matrix, one
masked lane per aggregate state, the exactness-check lanes); at 4M rows x
16 slots that is ~20 full-size HBM round trips and it measures ~2.7% of
the chip's streaming roofline. This kernel replaces all of them with ONE
HBM sweep: a sequential-grid Pallas kernel keeps the group table, the
first-encounter bookkeeping, and every per-group accumulator in VMEM/SMEM
scratch, so each input row is read exactly once.

Group identity follows the engine's established double-hash contract
(seg.py group_hash / hash_words): rows match a slot on the 62-bit primary
hash and the slot's independently-salted verify hash is checked in-kernel
— a mismatch raises the overflow flag and the retry driver falls back to
the sort kernel; silently-wrong needs both hashes to collide, the same
~2^-124 class the sort kernel already accepts. Multi-word keys are
pre-reduced by two independent linear folds (see _key_words) so each key
costs ONE word of emulated-64-bit mixing per hash instead of five.
Alternatives measured and rejected: full-word compare in the kernel
(string keys pack to 5 words; hauling 2 lanes per word made it slower
than the XLA dense kernel), and int32 multiply-rotate chains (VPU has no
native 32-bit vector multiply; 4 chains x 11 words benched below the XLA
dense kernel too).

New keys are inserted into the SMEM table by a bounded while-loop in
first-encounter row order — which is also the oracle's output order, so
the epilogue needs no reordering pass. More than `g_cap` distinct keys
raises the overflow flag and the retry driver falls back to the sort
kernel (ref: pkg/executor/aggregate/agg_hash_executor.go grows its tables
dynamically; fixed capacity + retry is the TPU analog).

Layout: every input lane is int32 shaped [N/128, 128] (int64 values ride
as bitcast hi/lo pairs — Mosaic has no 64-bit vectors). Exact integer
sums come from 4x12-bit limb accumulation of the biased value (v + 2^46),
and the XLA epilogue reconstructs the int64 totals as
sum(limb_l << 12l) - nn_count * 2^46. Values at or beyond +/-2^46 raise
the overflow flag. The per-lane-column int32 accumulators bound the ROW
count, not just the values: each of the N/128 rows in a lane column can
add up to 2^12-1 per limb, so the accumulator reaches ~N*2^5 and
silently wraps past int32 around N ~ 2^26 (~67M rows). Eligibility is
therefore gated on N < MAX_ROWS (2^26); larger batches ride the XLA
dense/sort kernels, whose int64 accumulation has no such bound
(ADVICE r5 medium — the old docstring claimed safety for any N < 2^31).

The whole pallas_call is traced under jax.enable_x64(False): with x64
enabled globally every Python int in the blocked lowering becomes an i64,
and Mosaic has no 64-bit grid/index arithmetic. The kernel body is pure
int32 either way.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .keys import sort_key_arrays

LANES = 128
MAX_TR = 256          # sublane rows per grid block (32K data rows)
MAX_COMBOS = 6        # distinct (value, null) argument combos
NH = 4                # independent 32-bit hash chains (128-bit identity)
NL = 4                # 12-bit limbs: covers |v| < 2^46 after biasing
BIAS = 1 << 46        # value bias making every in-range addend non-negative
# int32 limb-accumulator row bound: (N/128 rows per lane column) * (2^12-1
# max limb) must stay below 2^31 -> N < ~2^26.06; gate at 2^26 (module
# docstring "Layout" paragraph; ADVICE r5 medium)
MAX_ROWS = 1 << 26
_ALLOWED = frozenset({"count", "sum", "avg"})


def pallas_mode() -> str | None:
    """'tpu' for the compiled kernel, 'interpret' (tests), or None (off)."""
    env = os.environ.get("TIDB_TPU_PALLAS", "auto")
    if env == "off":
        return None
    if env == "interpret":
        return "interpret"
    if jax.default_backend() == "tpu":
        return "tpu"
    return "tpu" if env == "tpu" else None


def _rotl64(x, r: int):
    return (x << r) | jax.lax.shift_right_logical(x, 64 - r)


def _key_words(group_bys):
    """TWO independent word lists for the match / verify hashes.

    Multi-word keys (strings pack to 5 sort words) are first reduced to one
    word per hash by a cheap linear rotate-xor fold — two different
    rotation schedules, so a fold collision in one hash is independent of
    the other: (hp collides, hv differs) is caught by the kernel's verify
    check -> overflow -> sort kernel; silently wrong needs BOTH 64-bit
    folds+mixes to collide, the same ~2^-124 class the engine's sort
    kernel already accepts. Folding cuts the int64 mixing (emulated 64-bit
    multiplies on TPU) from 5 words to 1 per key — measured as the
    difference between this path beating and trailing the XLA dense
    kernel. None = ineligible keys."""
    wa, wb = [], []
    nf = None
    for k, g in enumerate(group_bys):
        if g.value.ndim == 2:
            # multi-word string keys: fold the [N, W] word matrix with
            # per-column rotations broadcast over axis 1, then XOR-reduce —
            # column-slicing it (sort_key_arrays' layout) costs a strided
            # copy per word on this backend
            words = g.value
            if g.ft.is_ci():
                from ..expr.compile import fold_words_ci

                words = fold_words_ci(words)
            words = jnp.where(g.null[:, None], jnp.int64(0), words)
            W = words.shape[1]

            def fold(step: int):
                sh = jnp.asarray(
                    [(step * j) % 63 + (1 if j else 0) for j in range(W)],
                    jnp.int64,
                )[None, :]
                rot = (words << sh) | jax.lax.shift_right_logical(
                    words, (64 - sh) % 64
                )
                return jnp.bitwise_xor.reduce(rot, axis=1)

            fa, fb = fold(7), fold(13)
        else:
            ws = sort_key_arrays(g)
            for w in ws[1:]:
                if jnp.issubdtype(w.dtype, jnp.floating):
                    return None  # NaN: bit-equality != SQL equality
            vals = ws[1:]
            fa, fb = vals[0], vals[0]
            for j, w in enumerate(vals[1:], start=1):
                fa = fa ^ _rotl64(w, (7 * j) % 63 + 1)
                fb = fb ^ _rotl64(w, (13 * j) % 63 + 1)
        wa.append(fa)
        wb.append(fb)
        b = g.null.astype(jnp.int64) << k
        nf = b if nf is None else nf | b
    if not wa or len(group_bys) > 32:
        return None
    return wa + [nf], wb + [nf]


def dense_pallas_eligible(group_bys, aggs, merge: bool) -> bool:
    """Strict subset the one-pass kernel handles; everything else falls to
    the XLA dense/sort kernels. The gate is a performance router, never a
    semantics change."""
    if merge or not group_bys:
        return False
    # row-count bound BEFORE any value work: the 12-bit limb accumulators
    # silently wrap past int32 at ~2^26 rows (see MAX_ROWS) — shape-only
    # check, so ineligible giants never materialize key folds
    n = group_bys[0].null.shape[0]
    if n >= MAX_ROWS:
        return False
    if _key_words(group_bys) is None:
        return False
    combos = set()
    for desc, avs in aggs:
        if desc.name not in _ALLOWED or desc.distinct:
            return False
        if desc.name == "count":
            if len(avs) > 1:
                return False
            if avs:
                # same lane checks as sum/avg: a float or wide-int COUNT
                # argument would ship a value lane that trips the in-kernel
                # range gate even though COUNT never reads the value
                a = avs[0]
                if a.eval_type not in ("int", "decimal") or a.value.ndim != 1:
                    return False
                if a.value.dtype != jnp.int64:
                    return False
                combos.add((id(a.value), id(a.null)))
            continue
        if len(avs) != 1:
            return False
        a = avs[0]
        if a.eval_type not in ("int", "decimal") or a.value.ndim != 1:
            return False
        if a.value.dtype != jnp.int64:
            return False
        combos.add((id(a.value), id(a.null)))
    return len(combos) <= MAX_COMBOS


def _lsr(x, k: int):
    return jax.lax.shift_right_logical(x, jnp.int32(k))


def _split32(v64: jax.Array):
    """int64 [N] -> (hi, lo) int32 [N].

    Arithmetic on the emulated-s64 pair, NOT a bitcast to [N, 2] + column
    slices: a stride-2 slice materializes as a sublane-strided copy on this
    backend and measured ~7ms across the q1 lanes; the shift/mask forms
    fuse into the surrounding elementwise program."""
    lo = (v64 & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32).astype(jnp.int32)
    hi = (v64 >> 32).astype(jnp.int32)
    return hi, lo


def _rotl(x, r: int):
    return (x << r) | _lsr(x, 32 - r)


def _shape_lane(a: jax.Array, np_: int):
    n = a.shape[0]
    if np_ != n:
        a = jnp.concatenate([a, jnp.zeros(np_ - n, a.dtype)])
    return a.reshape(np_ // LANES, LANES)


def group_aggregate_dense_pallas(group_bys, aggs, row_valid, g_cap: int, mode: str):
    """One-pass small-G aggregation; returns aggregate.GroupAggResult.

    aggs: [(AggDesc, [CompVal])] pre-checked by dense_pallas_eligible.
    g_cap: static slot count (the planner's NDV hint, capped by caller).
    """
    from .aggregate import GroupAggResult
    from .seg import group_hash, hash_words

    n = row_valid.shape[0]
    G = int(g_cap)

    # ---- lane construction (x64 world, fuses into the surrounding program)
    wa, wb = _key_words(group_bys)
    hp = group_hash(wa, row_valid, salt=G)        # match identity
    hv = hash_words(wb, G + 0x9E3779B9)           # verify identity
    hashes = list(_split32(hp)) + list(_split32(hv))

    combo_ix: dict = {}
    combo_vals: list = []
    for desc, avs in aggs:
        if desc.name == "count" and not avs:
            continue
        a = avs[0]
        k = (id(a.value), id(a.null))
        if k not in combo_ix:
            combo_ix[k] = len(combo_vals)
            combo_vals.append(a)
    NC = len(combo_vals)

    # nullword bits: 0 = row_valid, 1..NC = combo null
    nword = row_valid.astype(jnp.int32)
    for c, a in enumerate(combo_vals):
        nword = nword | (a.null.astype(jnp.int32) << (1 + c))

    np_ = -(-n // 1024) * 1024  # pad to whole (8,128) tiles
    tr = min(MAX_TR, np_ // LANES)
    while (np_ // LANES) % tr:
        tr //= 2
    nb = (np_ // LANES) // tr

    lanes = [_shape_lane(nword, np_)]
    for h in hashes:
        lanes.append(_shape_lane(h, np_))
    for a in combo_vals:
        hi, lo = _split32(a.value.astype(jnp.int64))
        lanes.append(_shape_lane(hi, np_))
        lanes.append(_shape_lane(lo, np_))

    # ---- accumulator row layout: per-group states, then one flag row
    # (overflow conditions accumulate as a VECTOR row — a scalar
    # jnp.max-to-SMEM per group per block lowers to a serial reduce and
    # measurably drags the whole kernel)
    per_g = 1 + NC * (NL + 1)         # count(*) + per-combo limbs + nn count
    flag_row = G * per_g
    acc_rows = -(-(flag_row + 1) // 8) * 8       # pad to whole sublane tiles
    out_rows = -(-(acc_rows + 2 + G) // 8) * 8   # + nused, flag, rep[g]
    tw = NH + 1                        # table: hash lanes + used marker

    def kern(*refs):
        nw_ref = refs[0]
        h_refs = refs[1 : 1 + NH]
        val_refs = refs[1 + NH : 1 + NH + 2 * NC]
        o_ref = refs[1 + NH + 2 * NC]
        acc, tbl, nused, flg, repm = refs[1 + NH + 2 * NC + 1 :]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc[:] = jnp.zeros_like(acc)
            nused[0] = jnp.int32(0)
            flg[0] = jnp.int32(0)
            for g in range(G):
                repm[g] = jnp.int32(0)
                for w in range(NH):
                    tbl[g * tw + w] = jnp.int32(0)
                # no real row can match an unused slot
                tbl[g * tw + NH] = jnp.int32(0)

        nword_b = nw_ref[:]
        hw = [h_refs[w][:] for w in range(NH)]
        valid = (nword_b & 1) == 1
        BIG = jnp.int32(2**31 - 1)
        lin = (
            jax.lax.broadcasted_iota(jnp.int32, (tr, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (tr, LANES), 1)
        )

        def match(g):
            # primary (hp) pair only; the hv pair is verified per slot below
            return (
                (tbl[g * tw + NH] == jnp.int32(1))
                & (hw[0] == tbl[g * tw])
                & (hw[1] == tbl[g * tw + 1])
            )

        def cond(c):
            return c[0]

        def body(c):
            _, it = c
            found = ~valid
            for g in range(G):
                found = found | match(g)
            miss = ~found
            minidx = jnp.min(jnp.where(miss, lin, BIG))
            has_miss = minidx < BIG
            fm = lin == minidx
            # read BEFORE the insert: reading after would flag the legal
            # G-th insert as overflow (capacity off-by-one)
            was_full = nused[0] >= G

            @pl.when(has_miss & ~was_full)
            def _():
                for w in range(NH):
                    tbl[nused[0] * tw + w] = jnp.min(jnp.where(fm, hw[w], BIG))
                tbl[nused[0] * tw + NH] = jnp.int32(1)
                repm[nused[0]] = i * (tr * LANES) + minidx
                nused[0] = nused[0] + 1

            @pl.when(has_miss & was_full)
            def _():
                flg[0] = jnp.int32(1)

            return (has_miss & ~was_full & (it < G), it + 1)

        jax.lax.while_loop(cond, body, (jnp.bool_(True), jnp.int32(0)))

        # value-range gate, combo-wise, group-independent: biased hi word
        # must fit 15 bits for the 4x12-bit limb split to be lossless
        bad = jnp.zeros((tr, LANES), bool)
        limbs_c = []
        for c in range(NC):
            nn_c = valid & (((nword_b >> (1 + c)) & 1) == 0)
            hb = val_refs[2 * c][:] + (1 << 14)
            lo = val_refs[2 * c + 1][:]
            bad = bad | (nn_c & ((hb < 0) | (_lsr(hb, 15) != 0)))
            # group-independent limb extraction, masked per group below
            limbs_c.append((
                lo & 0xFFF,
                _lsr(lo, 12) & 0xFFF,
                (_lsr(lo, 24) | ((hb & 0xF) << 8)) & 0xFFF,
                _lsr(hb, 4) & 0xFFF,
            ))

        for g in range(G):

            @pl.when(g < nused[0])
            def _(g=g):
                m = match(g) & valid
                # exactness: all hp-matches must share the slot's verify
                # hash (true collisions -> overflow -> sort kernel);
                # vector-accumulated into the flag row, never a scalar
                bad_g = m & (
                    (hw[2] != tbl[g * tw + 2]) | (hw[3] != tbl[g * tw + 3])
                )
                acc[flag_row, :] = acc[flag_row, :] + jnp.sum(
                    bad_g.astype(jnp.int32), axis=0, dtype=jnp.int32
                )

                base = g * per_g
                acc[base, :] = acc[base, :] + jnp.sum(
                    m.astype(jnp.int32), axis=0, dtype=jnp.int32
                )
                for c in range(NC):
                    nn = m & (((nword_b >> (1 + c)) & 1) == 0)
                    row = base + 1 + c * (NL + 1)
                    for l in range(NL):
                        acc[row + l, :] = acc[row + l, :] + jnp.sum(
                            jnp.where(nn, limbs_c[c][l], 0), axis=0, dtype=jnp.int32
                        )
                    acc[row + NL, :] = acc[row + NL, :] + jnp.sum(
                        nn.astype(jnp.int32), axis=0, dtype=jnp.int32
                    )

        acc[flag_row, :] = acc[flag_row, :] + jnp.sum(
            bad.astype(jnp.int32), axis=0, dtype=jnp.int32
        )

        @pl.when(i == nb - 1)
        def _():
            o_ref[:acc_rows, :] = acc[:, :]
            o_ref[acc_rows, :] = jnp.full((LANES,), nused[0], jnp.int32)
            o_ref[acc_rows + 1, :] = jnp.full((LANES,), flg[0], jnp.int32)
            for g in range(G):
                o_ref[acc_rows + 2 + g, :] = jnp.full((LANES,), repm[g], jnp.int32)

    with jax.enable_x64(False):
        in_specs = [
            pl.BlockSpec((tr, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
            for _ in lanes
        ]
        out = pl.pallas_call(
            kern,
            grid=(nb,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (out_rows, LANES), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
            out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((acc_rows, LANES), jnp.int32),
                pltpu.SMEM((G * tw,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SMEM((G,), jnp.int32),
            ],
            interpret=(mode == "interpret"),
            name="group_aggregate_dense",
        )(*lanes)

    # ---- epilogue (x64 world): reconstruct int64 states per group
    o = out.astype(jnp.int64)
    n_groups = out[acc_rows, 0].astype(jnp.int32)
    overflow = (out[acc_rows + 1, 0] != 0) | (jnp.sum(o[flag_row]) != 0)
    group_rep = out[acc_rows + 2 : acc_rows + 2 + G, 0].astype(jnp.int32)
    gidx = jnp.arange(G)
    group_valid = gidx < n_groups

    counts_star = jnp.sum(o[jnp.arange(G) * per_g], axis=1)
    combo_sums, combo_nn = [], []
    for c in range(NC):
        rows = jnp.arange(G) * per_g + 1 + c * (NL + 1)
        s = jnp.zeros(G, jnp.int64)
        for l in range(NL):
            s = s + (jnp.sum(o[rows + l], axis=1) << (12 * l))
        nn = jnp.sum(o[rows + NL], axis=1)
        combo_sums.append(s - nn * jnp.int64(BIAS))
        combo_nn.append(nn)

    zeros = jnp.zeros(G, bool)
    states = []
    for desc, avs in aggs:
        if desc.name == "count":
            if not avs:
                states.append([(counts_star, zeros)])
            else:
                c = combo_ix[(id(avs[0].value), id(avs[0].null))]
                states.append([(combo_nn[c], zeros)])
            continue
        c = combo_ix[(id(avs[0].value), id(avs[0].null))]
        empty = combo_nn[c] == 0
        if desc.name == "sum":
            states.append([(combo_sums[c], empty)])
        else:  # avg: [count, sum]
            states.append([(combo_nn[c], zeros), (combo_sums[c], empty)])

    return GroupAggResult(group_rep, group_valid, n_groups, overflow, states)
