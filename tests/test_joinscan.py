"""Parity: the Pallas post-sort segscan path of packed_join_groupsum vs
the XLA scan path, in interpret mode on CPU (ref coverage mirrors
tests/test_joinagg.py; the compiled path runs on the TPU in chip_smoke.py's Q3)."""

import numpy as np
import pytest

import jax


@pytest.fixture(autouse=True, scope="module")
def _fresh_jax_caches():
    """jax 0.4.x: jitted subfunctions cached by earlier tests under a
    different x64 weak-type state poison the Pallas kernels' lowering
    (i32/i64 verifier mismatch). A clean cache per kernel module keeps
    these hermetic; newer jax keys the cache correctly."""
    jax.clear_caches()
import jax.numpy as jnp

from tidb_tpu.chunk import Chunk
from tidb_tpu.exec import run_dag_on_chunks, run_dag_reference
from tidb_tpu.types import Datum, new_longlong

from test_joinagg import _dag, _mk, canon, LL
from tidb_tpu.expr import AggDesc, col


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("TIDB_TPU_PALLAS", "interpret")
    # retrace every program: a cache hit from a sibling module would skip
    # the traced-function spy below
    from tidb_tpu.exec.executor import DEFAULT_PROGRAM_CACHE

    DEFAULT_PROGRAM_CACHE._cache.clear()


def _spy_segscan(monkeypatch):
    import tidb_tpu.ops.joinscan as js

    calls = []
    orig = js.postsort_segscan

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(js, "postsort_segscan", spy)
    return calls


def test_segscan_parity_basic(monkeypatch):
    calls = _spy_segscan(monkeypatch)
    rng = np.random.default_rng(0)
    n, nb = 700, 50
    probe = _mk([LL, LL], [rng.integers(0, 64, n), rng.integers(-1000, 1000, n)])
    build = _mk([LL, LL], [np.arange(nb), rng.integers(0, 9, nb)])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ()),
                AggDesc("avg", (col(1, LL),))])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=256)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls, "segscan path did not engage"


def test_segscan_null_probe_keys(monkeypatch):
    calls = _spy_segscan(monkeypatch)
    probe = _mk([LL, LL], [[1, None, 2, None, 1, 3], [10, 20, 30, 40, 50, 60]])
    build = _mk([LL, LL], [[1, 2, 3], [7, 8, 9]])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=64)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls


def test_segscan_unmatched_and_negative(monkeypatch):
    calls = _spy_segscan(monkeypatch)
    rng = np.random.default_rng(2)
    n = 900
    probe = _mk([LL, LL], [rng.integers(-40, 40, n), rng.integers(-10**6, 10**6, n)])
    build = _mk([LL, LL], [np.arange(0, 20), rng.integers(0, 9, 20)])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=256)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls


def test_segscan_dup_build_falls_back(monkeypatch):
    calls = _spy_segscan(monkeypatch)
    rng = np.random.default_rng(3)
    probe = _mk([LL, LL], [rng.integers(0, 8, 200), rng.integers(0, 50, 200)])
    build = _mk([LL, LL], [[1, 1, 2, 3], [7, 8, 9, 10]])  # dup build keys
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=256)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)


def test_segscan_min_key_and_no_pins(monkeypatch):
    """Review regressions: (a) key -1 must not match the prev-key sentinel;
    (b) the max-key group must survive when every row is usable (the final
    boundary emission lands on the pad element)."""
    calls = _spy_segscan(monkeypatch)
    probe = _mk([LL, LL], [[-1, 0, 1, 7, 7], [100, 10, 20, 30, 40]])
    build = _mk([LL, LL], [[0, 1, 7], [5, 6, 8]])  # no -1: unmatched probe
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=64)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls
