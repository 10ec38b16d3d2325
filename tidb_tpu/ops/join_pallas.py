"""Pallas probe kernel for the radix-partitioned join (ISSUE 13
tentpole #3) — the fused build+probe inner loop of the int-key equi-join
fast path.

The XLA dense probe (ops/radix_join.py _probe_tables_xla) broadcasts a
[P, probe_cap, part_cap] compare and trusts fusion to keep it out of
HBM.  This kernel is the manual-fusion twin: one sequential-grid sweep,
one grid step per partition, the partition's build keys resident in SMEM
(radix partitioning is what made them fit — the "cache-friendly build
table" the reference's radix design doc partitions for), the probe block
in VMEM, and a statically unrolled slot loop doing the probe at VPU
rate.  No intermediate ever leaves the core.

int64 key words ride as hi/lo int32 pairs (Mosaic has no 64-bit
vectors — dense_pallas._split32's layout), so EVERY int-class key joins
exactly, including unsigned keys bit-flipped into the top half of the
domain; there is no value-range gate at all.  Eligibility is therefore
decided SHAPE-ONLY, before any value work:

  * part_cap <= MAX_PART_CAP (the SMEM table + unrolled-loop budget);
  * probe_cap a multiple of 1024 (whole (8, 128) int32 blocks per
    partition grid step);
  * total probe slots < MAX_ROWS = 2^26 — the same int32 per-lane-column
    accumulator class dense_pallas gates on (the meta rows accumulate
    per-block reductions across the whole grid).

Parity with the XLA probe is byte-exact by construction (same
first-match-slot semantics, same fan-out check) and pinned over the full
key-type matrix in tests/test_radix_join.py, interpret mode included.
Traced under jax.enable_x64(False) for the Mosaic lowering like every
Pallas kernel here (ops/joinscan.py _x64_ctx rationale).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .joinscan import _x64_ctx

LANES = 128
MAX_PART_CAP = 256     # SMEM build-table slots per partition (unrolled)
MAX_ROWS = 1 << 26     # probe-slot bound (dense_pallas MAX_ROWS class)


def pallas_probe_eligible(n_parts: int, part_cap: int, probe_cap: int) -> str | None:
    """'tpu' | 'interpret' | None — the shape-only lowering gate, decided
    before any value work (capacities only, never data)."""
    from .dense_pallas import pallas_mode

    mode = pallas_mode()
    if not mode:
        return None
    if part_cap > MAX_PART_CAP:
        return None
    if probe_cap % 1024 != 0:
        return None
    if n_parts * probe_cap >= MAX_ROWS:
        return None
    return mode


def _make_kernel(n_parts: int, part_cap: int, trp: int):
    def kern(bhi_ref, blo_ref, bok_ref, phi_ref, plo_ref, pok_ref,
             bpos_ref, meta_ref, macc):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            macc[:] = jnp.zeros_like(macc)

        phi = phi_ref[:]
        plo = plo_ref[:]
        pok = pok_ref[:] != 0
        cap = jnp.int32(part_cap)
        bpos = jnp.full((trp, LANES), part_cap, jnp.int32)
        nmatch = jnp.zeros((trp, LANES), jnp.int32)
        # statically unrolled probe: slot g's key broadcasts from SMEM;
        # ascending g means the first hit wins, matching the XLA probe's
        # min-slot reduction exactly
        for g in range(part_cap):
            on = bok_ref[0, g] != 0
            m = pok & on & (phi == bhi_ref[0, g]) & (plo == blo_ref[0, g])
            bpos = jnp.where(m & (bpos == cap), jnp.int32(g), bpos)
            nmatch = nmatch + m.astype(jnp.int32)
        bpos_ref[:] = bpos
        # unique-build fan-out check, vector-accumulated (no scalar VMEM
        # stores on Mosaic); int32 literals for the x64-on interpret path
        one, zero = jnp.int32(1), jnp.int32(0)
        macc[0, :] = macc[0, :] | jnp.max(
            jnp.where(nmatch > 1, one, zero), axis=0
        )

        @pl.when(i == n_parts - 1)
        def _():
            meta_ref[:, :] = macc[:, :]

    return kern


def probe_tables_pallas(b_key_tbl, b_slot_ok, p_key_tbl, p_slot_ok,
                        interpret: bool = False):
    """(bpos int32 [P, probe_cap] — part_cap = no match, dup flag): the
    Pallas twin of _probe_tables_xla over int64 key tables."""
    from .dense_pallas import _split32

    P, part_cap = b_key_tbl.shape
    probe_cap = p_key_tbl.shape[1]
    trp = probe_cap // LANES
    bhi, blo = _split32(b_key_tbl.reshape(-1))
    phi, plo = _split32(p_key_tbl.reshape(-1))

    def btab(a):
        # [P, 1, part_cap]: the (1, part_cap) block then spans the array's
        # last two dimensions whole, which is what the Mosaic lowering
        # asks of a block that is not an (8, 128) multiple
        return a.reshape(P, 1, part_cap)

    def plane(a):
        return a.reshape(P * trp, LANES)

    ins = [
        btab(bhi), btab(blo), btab(b_slot_ok.astype(jnp.int32)),
        plane(phi), plane(plo),
        p_slot_ok.astype(jnp.int32).reshape(P * trp, LANES),
    ]
    sspec = pl.BlockSpec((None, 1, part_cap), lambda i: (i, 0, 0), memory_space=pltpu.SMEM)
    vspec = pl.BlockSpec((trp, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    mspec = pl.BlockSpec((8, LANES), lambda i: (0, 0), memory_space=pltpu.VMEM)
    with _x64_ctx(interpret):
        bpos2, meta = pl.pallas_call(
            _make_kernel(P, part_cap, trp),
            grid=(P,),
            in_specs=[sspec, sspec, sspec, vspec, vspec, vspec],
            out_specs=(vspec, mspec),
            out_shape=(
                jax.ShapeDtypeStruct((P * trp, LANES), jnp.int32),
                jax.ShapeDtypeStruct((8, LANES), jnp.int32),
            ),
            scratch_shapes=[pltpu.VMEM((8, LANES), jnp.int32)],
            interpret=interpret,
            name="join_probe_tables",
        )(*ins)
    bpos = bpos2.reshape(P, probe_cap)
    dup = jnp.sum(meta[0].astype(jnp.int64)) != 0
    return bpos, dup
