"""A region batch's capacity has a floor (`store.MIN_BATCH_ROWS`): a range
statement whose range runs past its table's end, or holds one row, is
served by the program that a 100-row range compiled (sysbench's ranges sit
on rung 128), where each fewer-row range used to meet a rung of its own
(64, 32, ..., 1) and compile it while it was served.  A large region's
batch keeps its power of two."""

import numpy as np
import pytest

from tidb_tpu.sql import Session
from tidb_tpu.store import store as store_mod
from tidb_tpu.util import metrics

ROWS = 300


@pytest.fixture
def served():
    """A fresh store: nothing decoded, uploaded or compiled yet."""
    s = Session()
    s.execute("create table sb (id int not null, k int not null, c char(20) not null, "
              "primary key (id), key k_1 (k))")
    rng = np.random.default_rng(2147483901)
    k = rng.integers(1, 1000, ROWS)
    c = [f"c{v:08d}" for v in rng.integers(0, 10**8, ROWS)]
    s.execute("insert into sb values " + ",".join(f"({i + 1},{k[i]},'{c[i]}')" for i in range(ROWS)))
    return s, k, c


@pytest.fixture
def rungs(monkeypatch):
    """The capacities region batches are uploaded at."""
    seen = []
    real = store_mod.to_device_batch

    def recording(chunk, *a, capacity=None, **kw):
        seen.append(capacity)
        return real(chunk, *a, capacity=capacity, **kw)

    monkeypatch.setattr(store_mod, "to_device_batch", recording)
    return seen


def _expect(k, c, name: str, a: int, b: int):
    lo, hi = max(a, 1), min(b, ROWS)
    if name == "sum_range":
        return [[int(k[lo - 1:hi].sum())]]
    return [[v] for v in sorted(c[lo - 1:hi])]


SQL = {"sum_range": "select sum(k) from sb where id between {a} and {b}",
       "order_range": "select c from sb where id between {a} and {b} order by c"}


@pytest.mark.parametrize("name", list(SQL))
def test_short_ranges_reuse_the_100_row_rung(served, rungs, name):
    s, k, c = served

    def ask(a, b):
        got = s.execute(SQL[name].format(a=a, b=b)).values()
        assert [[int(str(v)) if name == "sum_range" else v for v in row] for row in got] == _expect(k, c, name, a, b)

    ask(101, 200)   # 100 rows: rung 128, compiled here
    compiles = metrics.PROGRAM_COMPILES.value
    for a, b in ((250, 349), (ROWS, ROWS + 99), (7, 7), (40, 41)):   # 51, 1, 1 and 2 rows
        ask(a, b)
    assert metrics.PROGRAM_COMPILES.value == compiles   # nothing compiled for the short ranges
    assert rungs and set(rungs) == {store_mod.MIN_BATCH_ROWS}


def test_large_region_keeps_its_power_of_two(served, rungs):
    s, k, _ = served
    assert s.execute("select sum(k) from sb where id between 1 and 300").values()[0][0] is not None
    assert rungs == [512]   # 300 rows
    assert [store_mod.batch_rung(n) for n in (0, 1, 100, 128, 129, 131072, 131073)] == \
        [128, 128, 128, 128, 256, 131072, 262144]
