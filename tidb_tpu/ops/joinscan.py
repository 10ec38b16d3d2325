"""Pallas post-sort pass for the packed join+group kernel (TPC-H Q3).

After packed_join_groupsum's ONE int32 sort, the XLA path pays ~10ms of
scan floors at 4.65M rows on the v5e (an int64 cumsum + int64 reverse
cummin per agg combo, an int32 reverse cummin for run extents, plus a
batched overflow reduce — each op carries a 2-4ms dispatch floor; a
2026-07-31 measurement, not repeated since).
This kernel replaces ALL of it with one sequential-grid sweep over the
sorted arrays: a flagged Hillis-Steele segmented scan (lane phase by
pltpu.roll along lanes, sublane phase by roll + last-lane broadcast,
block carries in SMEM) computes per-run contributing counts, the matched
flag, and exact sums as three 12/12/8-bit limb lanes of the bias-flipped
value (sv ^ 0x80000000 — every addend non-negative, so in-block partial
sums stay under 2^27 in int32; block-boundary carries re-normalize into
canonical limbs so only the top limb grows, bounded by the run-length cap
below).

Emission shift: element e with a key boundary emits the run that ENDED at
e-1 (sum/count/matched from the rolled inclusive scan, key from the
rolled spk). Downstream consumers only see (group_valid, states, key_out,
extent_cnt) as mutually-aligned [n] lanes, so boundary positions are as
good as first-probe-row positions — and a forward-only formulation needs
no reverse scans at all. The array is padded with probe pins so the last
real run always has a boundary element after it.

Overflow -> the join-overflow retry (general kernel), one flag: duplicate
usable hay keys (unique-build contract), any pre-sort bad lane bit (key
or value outside int32 — the unsorted lane rides as a THIRD input so its
any() costs no standalone XLA reduce), or a single run exceeding 2^23
contributing rows (the limb-carry bound; a group that large implies a
skew the general kernel handles anyway).

Traced under jax.enable_x64(False) like every Pallas kernel here (with
x64 on, every Python int of the grid arithmetic becomes an i64).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _x64_ctx(interpret: bool):
    """x64(False) for the Mosaic (real-TPU) lowering only. In interpret
    mode the kernel is staged into the OUTER x64-on trace but lowered
    later with x64 back on; tracing it under x64(False) desyncs literal
    avals from their lowered constants ('func.call' operand i32/i64
    mismatch). The kernels are explicitly i32-typed, so the flag only
    matters to Mosaic's 64-bit-rewrite pass."""
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)

LANES = 128
TR = 256
T = TR * LANES
_PIN = (1 << 31) - 4          # joinagg._PIN_HAY as a plain int
_RUN_CAP = 1 << 23            # max contributing rows per run (limb bound)


def _lsr(x, k: int):
    return jax.lax.shift_right_logical(x, jnp.int32(k))


def _make_kernel(nb: int, nc: int, nn_bits):
    nnb = [b for b in nn_bits if b >= 0]
    has_nw = bool(nnb)
    nscan = 1 + 3 * nc + len(nnb)  # cnt|mb, limbs, nullable nn counts

    def kern(*refs):
        k = 0
        spk_ref = refs[k]; k += 1
        bad_ref = refs[k]; k += 1
        sv_refs = refs[k : k + nc]; k += nc
        nw_ref = None
        if has_nw:
            nw_ref = refs[k]; k += 1
        gv_ref = refs[k]; k += 1
        cnt_ref = refs[k]; k += 1
        key_ref = refs[k]; k += 1
        limb_refs = refs[k : k + 3 * nc]; k += 3 * nc
        nn_refs = refs[k : k + len(nnb)]; k += len(nnb)
        meta_ref = refs[k]; k += 1
        carry, macc = refs[k:]
        # carry: [0]=prev_pk, then one slot per scan lane
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            carry[0] = jnp.int32(-(2**31))  # below every real pk
            for j in range(nscan):
                carry[1 + j] = jnp.int32(0)
            macc[:] = jnp.zeros_like(macc)

        spk = spk_ref[:]
        lid = jax.lax.broadcasted_iota(jnp.int32, (TR, LANES), 1)
        sid = jax.lax.broadcasted_iota(jnp.int32, (TR, LANES), 0)

        def prev_of(x, first_fill):
            lanerolled = pltpu.roll(x, 1, 1)
            subrolled = pltpu.roll(lanerolled, 1, 0)
            p = jnp.where(lid == 0, subrolled, lanerolled)
            return jnp.where((lid == 0) & (sid == 0), first_fill, p)

        prev_pk = prev_of(spk, carry[0])
        is_hay = (spk & 1) == 0
        is_real = spk < _PIN
        prev_is_hay = (prev_pk & 1) == 0
        keydiff = (spk | 1) != (prev_pk | 1)
        contrib = (~is_hay) & is_real
        dup = is_hay & is_real & (spk == prev_pk) & prev_is_hay
        mb = contrib & (~keydiff) & prev_is_hay & (prev_pk == spk - 1)

        # scan lanes: cnt|matched packed, 3 limbs per combo, nn counts
        vals = [contrib.astype(jnp.int32) + (mb.astype(jnp.int32) << 24)]
        for c in range(nc):
            vb = sv_refs[c][:] ^ jnp.int32(-2147483648)
            vals.append(jnp.where(contrib, vb & 0xFFF, 0))
            vals.append(jnp.where(contrib, _lsr(vb, 12) & 0xFFF, 0))
            vals.append(jnp.where(contrib, _lsr(vb, 24) & 0xFF, 0))
        for b in nnb:
            nn = contrib & (((nw_ref[:] >> b) & 1) == 0)
            vals.append(nn.astype(jnp.int32))

        fs = keydiff.astype(jnp.int32)
        vs = list(vals)
        for d in (1, 2, 4, 8, 16, 32, 64):
            ok = lid >= d
            rf = pltpu.roll(fs, d, 1)
            rvs = [pltpu.roll(v, d, 1) for v in vs]
            keep = (fs == 0) & ok
            vs = [jnp.where(keep, v + rv, v) for v, rv in zip(vs, rvs)]
            fs = jnp.where(ok, fs | rf, fs)
        for d in (1, 2, 4, 8, 16, 32, 64, 128):
            ok = sid >= d
            rf = pltpu.roll(fs, d, 0)
            rvs = [pltpu.roll(v, d, 0) for v in vs]
            rl = [jnp.broadcast_to(rv[:, LANES - 1 : LANES], (TR, LANES)) for rv in rvs]
            rfl = jnp.broadcast_to(rf[:, LANES - 1 : LANES], (TR, LANES))
            keep = (fs == 0) & ok
            vs = [jnp.where(keep, v + rv, v) for v, rv in zip(vs, rl)]
            fs = jnp.where(ok, fs | rfl, fs)

        nof = fs == 0  # no boundary in [block_start..e]: add the carry-in
        cin = [carry[1 + j] for j in range(nscan)]
        vs = [jnp.where(nof, v + c, v) for v, c in zip(vs, cin)]

        # emit the run ended at e-1
        pvs = [prev_of(v, c) for v, c in zip(vs, cin)]
        pc = pvs[0] & 0xFFFFFF
        pm = _lsr(pvs[0], 24)
        emit = keydiff & (pc > 0) & (pm > 0)
        gv_ref[:] = emit.astype(jnp.int32)
        cnt_ref[:] = jnp.where(emit, pc, 0)
        key_ref[:] = jnp.where(emit, prev_pk, 0)
        for j in range(3 * nc):
            limb_refs[j][:] = jnp.where(emit, pvs[1 + j], 0)
        for j in range(len(nnb)):
            nn_refs[j][:] = jnp.where(emit, pvs[1 + 3 * nc + j], 0)

        # carries for the open run, limb-normalized so only the top limb
        # grows across blocks (bounded by the run cap)
        carry[0] = spk[TR - 1, LANES - 1]
        cl = vs[0][TR - 1, LANES - 1]
        carry[1] = cl
        runcap = (cl & 0xFFFFFF) >= (_RUN_CAP - T)
        for c in range(nc):
            l0 = vs[1 + 3 * c][TR - 1, LANES - 1]
            l1 = vs[2 + 3 * c][TR - 1, LANES - 1] + _lsr(l0, 12)
            carry[2 + 3 * c] = l0 & 0xFFF
            carry[3 + 3 * c] = l1 & 0xFFF
            carry[4 + 3 * c] = vs[3 + 3 * c][TR - 1, LANES - 1] + _lsr(l1, 12)
        for j in range(len(nnb)):
            carry[2 + 3 * nc + j] = vs[1 + 3 * nc + j][TR - 1, LANES - 1]

        macc[0, :] = macc[0, :] | jnp.max(dup.astype(jnp.int32), axis=0)
        macc[1, :] = macc[1, :] + jnp.sum(contrib.astype(jnp.int32), axis=0, dtype=jnp.int32)
        macc[2, :] = macc[2, :] | jnp.max(bad_ref[:], axis=0)
        # run cap: open-run carry or an emitted count crossing the bound
        # (vector OR — Mosaic has no scalar VMEM stores). int32 literals:
        # int-only where() branches default to int64 when tracing with x64
        # on (the interpret path)
        one, zero = jnp.int32(1), jnp.int32(0)
        macc[0, :] = macc[0, :] | jnp.where(runcap, one, zero) | jnp.max(
            jnp.where(emit & (pc >= _RUN_CAP - T), one, zero), axis=0
        )

        @pl.when(i == nb - 1)
        def _():
            meta_ref[:, :] = macc[:, :]

    return kern


def postsort_segscan(spk, lanes_s, bad_lane, nw_s=None, nn_bits=(),
                     interpret: bool = False):
    """spk int32 [n] (sorted packed keys), lanes_s: list of int32 [n]
    (sorted agg lanes), bad_lane bool [n] (UNSORTED pre-sort overflow
    bits), nw_s uint8 [n] sorted null-bit word with nn_bits[c] the bit of
    combo c (-1 = NOT NULL). Returns (group_valid, cnt int64, key_i32,
    [sum int64 per lane], [nn int64 per lane], overflow, join_rows) — all
    [n]-aligned at run-boundary positions."""
    n = spk.shape[0]
    nc = len(lanes_s)
    nnb = [b for b in nn_bits if b >= 0]
    np2 = -(-(n + 1) // T) * T
    pad = np2 - n

    def shape(a, fill):
        if pad:
            a = jnp.concatenate([a, jnp.full(pad, fill, a.dtype)])
        return a.reshape(np2 // LANES, LANES)

    spk2 = shape(spk, jnp.int32(_PIN + 1))  # probe-pin pad: emits last run
    bad2 = shape(bad_lane.astype(jnp.int32), 0)
    svs = [shape(v, 0) for v in lanes_s]
    ins = [spk2, bad2] + svs
    if nnb:
        ins.append(shape(nw_s.astype(jnp.int32), 0))
    R = np2 // LANES
    nb = R // TR

    spec = pl.BlockSpec((TR, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    mspec = pl.BlockSpec((8, LANES), lambda i: (0, 0), memory_space=pltpu.VMEM)
    n_out = 3 + 3 * nc + len(nnb)
    nscan = 1 + 3 * nc + len(nnb)
    with _x64_ctx(interpret):
        outs = pl.pallas_call(
            _make_kernel(nb, nc, list(nn_bits)),
            grid=(nb,),
            in_specs=[spec] * len(ins),
            out_specs=tuple([spec] * n_out + [mspec]),
            out_shape=tuple(
                [jax.ShapeDtypeStruct((R, LANES), jnp.int32)] * n_out
                + [jax.ShapeDtypeStruct((8, LANES), jnp.int32)]
            ),
            scratch_shapes=[
                pltpu.SMEM((1 + nscan,), jnp.int32),
                pltpu.VMEM((8, LANES), jnp.int32),
            ],
            interpret=interpret,
            name="join_postsort_segscan",
        )(*ins)

    # Emission happens at e for the run that ended at e-1; shifting every
    # output lane back by one places each emission on its run's LAST
    # element — always inside [0, n), including the FINAL run whose
    # boundary fires on the first pad element (flat index n; a plain [:n]
    # slice dropped the max-key group whenever no pin rows existed).
    def unshape(a):
        return a.reshape(np2)[1 : n + 1]

    gv = unshape(outs[0]) != 0
    cnt = unshape(outs[1]).astype(jnp.int64)
    key = unshape(outs[2])
    meta = outs[3 + 3 * nc + len(nnb)].astype(jnp.int64)
    sums = []
    for c in range(nc):
        l0 = unshape(outs[3 + 3 * c]).astype(jnp.int64)
        l1 = unshape(outs[4 + 3 * c]).astype(jnp.int64)
        l2 = unshape(outs[5 + 3 * c]).astype(jnp.int64)
        biased = l0 + (l1 << 12) + (l2 << 24)
        sums.append(biased - (cnt << 31))
    nns = []
    j = 0
    for b in nn_bits:
        if b < 0:
            nns.append(cnt)
        else:
            nns.append(unshape(outs[3 + 3 * nc + j]).astype(jnp.int64))
            j += 1
    overflow = (jnp.sum(meta[0]) + jnp.sum(meta[2])) != 0
    join_rows = jnp.sum(meta[1])
    return gv, cnt, key, sums, nns, overflow, join_rows


def _make_member_kernel(nb: int):
    def kern(spk_ref, bad_ref, ok_ref, meta_ref, carry, macc):
        # carry: [0]=prev_pk [1]=open-run head flag
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            carry[0] = jnp.int32(-(2**31))  # below every real pk
            carry[1] = jnp.int32(0)
            macc[:] = jnp.zeros_like(macc)

        spk = spk_ref[:]
        lid = jax.lax.broadcasted_iota(jnp.int32, (TR, LANES), 1)
        sid = jax.lax.broadcasted_iota(jnp.int32, (TR, LANES), 0)

        def prev_of(x, first_fill):
            lanerolled = pltpu.roll(x, 1, 1)
            subrolled = pltpu.roll(lanerolled, 1, 0)
            p = jnp.where(lid == 0, subrolled, lanerolled)
            return jnp.where((lid == 0) & (sid == 0), first_fill, p)

        prev_pk = prev_of(spk, carry[0])
        is_inner = (spk & 1) == 0
        is_real = spk < _PIN
        prev_is_inner = (prev_pk & 1) == 0
        keydiff = (spk | 1) != (prev_pk | 1)
        dup = is_inner & is_real & (spk == prev_pk) & prev_is_inner
        # run head is a usable inner row: inner rows sort first in a run
        head = (is_inner & is_real & keydiff).astype(jnp.int32)

        fs = keydiff.astype(jnp.int32)
        v = head
        for d in (1, 2, 4, 8, 16, 32, 64):
            ok = lid >= d
            rf = pltpu.roll(fs, d, 1)
            rv = pltpu.roll(v, d, 1)
            keep = (fs == 0) & ok
            v = jnp.where(keep, v + rv, v)
            fs = jnp.where(ok, fs | rf, fs)
        for d in (1, 2, 4, 8, 16, 32, 64, 128):
            ok = sid >= d
            rf = pltpu.roll(fs, d, 0)
            rv = pltpu.roll(v, d, 0)
            rl = jnp.broadcast_to(rv[:, LANES - 1 : LANES], (TR, LANES))
            rfl = jnp.broadcast_to(rf[:, LANES - 1 : LANES], (TR, LANES))
            keep = (fs == 0) & ok
            v = jnp.where(keep, v + rl, v)
            fs = jnp.where(ok, fs | rfl, fs)
        v = jnp.where(fs == 0, v + carry[1], v)

        ok_out = (~is_inner) & is_real & (v > 0)
        ok_ref[:] = ok_out.astype(jnp.int32)

        carry[0] = spk[TR - 1, LANES - 1]
        carry[1] = v[TR - 1, LANES - 1]
        macc[0, :] = macc[0, :] | jnp.max(dup.astype(jnp.int32), axis=0)
        macc[0, :] = macc[0, :] | jnp.max(bad_ref[:], axis=0)

        @pl.when(i == nb - 1)
        def _():
            meta_ref[:, :] = macc[:, :]

    return kern


def membership_segscan(spk, bad_lane, interpret: bool = False):
    """Post-sort pass for membership_chain: per-element ok_out (outer row
    whose key run starts with a usable inner row) plus the overflow flag
    (duplicate inner keys | any pre-sort bad bit) in one sweep — replaces
    an int32 cummax and a standalone batched any() of the XLA path."""
    n = spk.shape[0]
    np2 = -(-n // T) * T
    pad = np2 - n

    def shape(a, fill):
        if pad:
            a = jnp.concatenate([a, jnp.full(pad, fill, a.dtype)])
        return a.reshape(np2 // LANES, LANES)

    spk2 = shape(spk, jnp.int32(_PIN + 1))
    bad2 = shape(bad_lane.astype(jnp.int32), 0)
    R = np2 // LANES
    nb = R // TR
    spec = pl.BlockSpec((TR, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    mspec = pl.BlockSpec((8, LANES), lambda i: (0, 0), memory_space=pltpu.VMEM)
    with _x64_ctx(interpret):
        ok2, meta = pl.pallas_call(
            _make_member_kernel(nb),
            grid=(nb,),
            in_specs=[spec, spec],
            out_specs=(spec, mspec),
            out_shape=(
                jax.ShapeDtypeStruct((R, LANES), jnp.int32),
                jax.ShapeDtypeStruct((8, LANES), jnp.int32),
            ),
            scratch_shapes=[
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((8, LANES), jnp.int32),
            ],
            interpret=interpret,
            name="join_membership_segscan",
        )(spk2, bad2)
    ok_out = ok2.reshape(np2)[:n] != 0
    overflow = jnp.sum(meta[0].astype(jnp.int64)) != 0
    return ok_out, overflow
