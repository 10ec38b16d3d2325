"""Deviceless TPU compiles: the four Pallas kernels of the served path at
one real shape each, plus the fused Q6 program, compiled for a described
(not attached) v5e chip.  Interpret mode cannot see what the Mosaic
lowering refuses (block shapes, SMEM/VMEM budgets, 64-bit index
arithmetic); this does, at no chip time.  Nothing runs, so nothing here
says anything about results or speed.

The topology is described inside a module-scoped fixture: only the worker
that is given this file loads the TPU library, and every worker collects
the same tests.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # __graft_entry__

ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A deviceless compile is written to the persistent cache but cannot
    be read back without a chip: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _dense(shape):
    from tidb_tpu.expr import AggDesc, col
    from tidb_tpu.expr.compile import CompVal
    from tidb_tpu.ops.dense_pallas import dense_pallas_eligible, group_aggregate_dense_pallas
    from tidb_tpu.types import new_decimal, new_longlong

    ll, dec = new_longlong(), new_decimal(15, 2)

    def fn(k, kn, v, vn, valid):
        g, d = CompVal(k, kn, ll), CompVal(v, vn, dec)
        aggs = [(AggDesc("count", ()), []), (AggDesc("sum", (col(1, dec),)), [d]),
                (AggDesc("avg", (col(1, dec),)), [d])]
        assert dense_pallas_eligible([g], aggs, merge=False)
        r = group_aggregate_dense_pallas([g], aggs, valid, 16, "tpu")
        return r.group_rep, r.group_valid, r.n_groups, r.overflow, r.states

    i64, b = shape((ROWS,), jnp.int64), shape((ROWS,), jnp.bool_)
    return fn, (i64, b, i64, b, b)


def _postsort(shape):
    from tidb_tpu.ops.joinscan import postsort_segscan

    n = ROWS + ROWS // 4  # lineitem + orders in one sorted space
    i32 = shape((n,), jnp.int32)
    return (lambda spk, lane, bad, nw: postsort_segscan(spk, [lane], bad, nw_s=nw, nn_bits=(0,)),
            (i32, i32, shape((n,), jnp.bool_), shape((n,), jnp.uint8)))


def _membership(shape):
    from tidb_tpu.ops.joinscan import membership_segscan

    n = ROWS // 4 + ROWS // 32  # orders + customer
    return membership_segscan, (shape((n,), jnp.int32), shape((n,), jnp.bool_))


def _probe(shape):
    from tidb_tpu.ops.join_pallas import pallas_probe_eligible, probe_tables_pallas
    from tidb_tpu.ops.radix_join import radix_plan

    # lineitem probing customer: the radix plan the served Q3 derives
    parts, part_cap, probe_cap, _esc = radix_plan(ROWS // 32, ROWS, ROWS)
    assert pallas_probe_eligible(parts, part_cap, probe_cap) == "tpu"
    return probe_tables_pallas, (
        shape((parts, part_cap), jnp.int64), shape((parts, part_cap), jnp.bool_),
        shape((parts, probe_cap), jnp.int64), shape((parts, probe_cap), jnp.bool_))


def _q6(shape):
    import __graft_entry__ as ge
    from tidb_tpu.chunk import to_device_batch
    from tidb_tpu.exec.builder import build_program

    dag, fts = ge._q6_dag()
    small = to_device_batch(ge._rand_chunk(fts, 8), capacity=8)
    batch = jax.tree.map(lambda x: shape((ROWS,) + x.shape[1:] if x.ndim else (), x.dtype), small)
    # the literals follow the batch as operands (exec/dag.py program_operands)
    operands = tuple(shape(o.shape, o.dtype) for o in dag.program_operands())
    return build_program(dag, ROWS, group_capacity=16).fn, (batch, *operands)


@pytest.mark.parametrize("case,pallas_calls", [
    (_dense, 1), (_postsort, 1), (_membership, 1), (_probe, 1), (_q6, 0),
], ids=["dense_pallas", "postsort_segscan", "membership_segscan", "probe_tables_pallas", "q6_program"])
def test_compiles_for_v5e(case, pallas_calls, one_chip, monkeypatch):
    # the env value, not the backend, routes to the Mosaic lowering here:
    # jax.default_backend() is "cpu" during a deviceless compile
    monkeypatch.setenv("TIDB_TPU_PALLAS", "tpu")
    fn, args = case(lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    # a kernel that gave way to the XLA branch beside it would still compile
    assert compiled.as_text().count("tpu_custom_call") == pallas_calls


# ------------------------------------------------------------------------
# What JAX's persistent-cache key is made of for a program that carries a
# Pallas kernel (ROADMAP S6, settled in PR 28).  The key hashes the module
# with its own debug info stripped, but the Mosaic kernel travels inside
# `tpu_custom_call`'s backend_config as bytecode that KEEPS its source
# locations: the files and lines of the Python call stack above the kernel.
# Two processes of one tree at one path agree on those (the chip showed the
# second run of a checkout hitting); a moved line in any caller, or another
# checkout directory, is another key for every Pallas-bearing program while
# the pure-XLA programs keep theirs: what PR 22 saw.
def _computation_hash(lowered) -> str:
    import hashlib

    from jax._src import cache_key

    module = lowered.compiler_ir("stablehlo")
    return hashlib.sha256(cache_key._canonicalize_ir(module, cache_key.IgnoreCallbacks.NO)).hexdigest()


def _lower_dense(one_chip, pad: int = 0):
    """The dense group-by lowered for the v5e, called through a function
    that sits `pad` lines further down its (made-up) file."""
    fn, args = _dense(lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip))
    scope = {"fn": fn}
    exec(compile("\n" * pad + "def call(*a):\n    return fn(*a)\n", "/caller_of_the_kernel.py", "exec"), scope)
    return jax.jit(scope["call"]).lower(*args)


def test_pallas_cache_key_is_the_same_for_two_builds_of_one_tree(one_chip, monkeypatch):
    """Nothing of the program's own goes into the key that differs from one
    build to the next: no counter or `id()` in the kernel's name, no
    argument order taken from a set, no closure over a fresh object."""
    monkeypatch.setenv("TIDB_TPU_PALLAS", "tpu")
    first, second = [_lower_dense(one_chip) for _ in range(2)]   # one call site: a location is file, line and column
    assert first.as_text().count("tpu_custom_call") == 1
    assert 'kernel_name = "group_aggregate_dense"' in first.as_text()
    assert _computation_hash(first) == _computation_hash(second)


def test_pallas_cache_key_holds_the_lines_of_the_kernels_callers(one_chip, monkeypatch):
    """Pins the finding, not a wish: should this fail, JAX no longer keeps
    source locations in the Mosaic bytecode, an edit above the kernel no
    longer costs Q1's ~100 s compile again, and PERF.md section 7 can say so."""
    monkeypatch.setenv("TIDB_TPU_PALLAS", "tpu")
    assert _computation_hash(_lower_dense(one_chip)) != _computation_hash(_lower_dense(one_chip, pad=7))
