"""The persistent compile cache's key is the same in two processes of one
tree (ROADMAP S6, settled in PR 28): a second process takes every program
the first one wrote from `JAX_COMPILATION_CACHE_DIR` and says so on
`exec.compile` (`persistent_cache: hit`).  A Q1-shaped statement (filter on
a date operand, dense group-by over two CHAR(1) keys, decimal sums and
averages, ORDER BY) over the columnar replica, on the CPU with Pallas in
the mode the CPU tests use.  What the chip adds to the key, the Mosaic
kernel's bytecode inside `tpu_custom_call`, is pinned without a chip in
`test_tpu_compile.py::test_pallas_cache_key_*`."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from tidb_tpu.sql.session import Session
from tidb_tpu.util import metrics

s = Session()
s.execute('''create table lineitem (l_orderkey bigint not null, l_linenumber bigint not null,
    l_quantity decimal(15,2) not null, l_extendedprice decimal(15,2) not null, l_discount decimal(15,2) not null,
    l_tax decimal(15,2) not null, l_returnflag char(1) not null, l_linestatus char(1) not null,
    l_shipdate date not null, primary key (l_orderkey, l_linenumber))''')
s.execute("insert into lineitem values " + ",".join(
    f"({i},1,{i % 50 + 1},{900 + i}.{i % 100:02d},0.0{i % 10},0.0{i % 8},'{'ANR'[i % 3]}','{'FO'[i % 2]}',"
    f"'199{2 + i % 7}-0{1 + i % 9}-1{i % 9}')" for i in range(300)))
s.execute("analyze table lineitem columns l_returnflag, l_linestatus")
s.execute("alter table lineitem set tiflash replica 1")
s.store.pd.tick()
s.execute("set tidb_isolation_read_engines = 'tpu,columnar'")
q1 = '''select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),
    sum(l_extendedprice * (1 - l_discount)), sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
    avg(l_quantity), avg(l_discount), count(*) from lineitem
    where l_shipdate <= date '1998-12-01' - interval '{delta}' day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus'''
scans = metrics.COLUMNAR_SCANS.value
tree = json.loads(s.execute("trace format='json' " + q1.format(delta=90)).values()[0][0])
rows = s.execute(q1.format(delta=61)).values()

def find(node, name):
    out = [node] if node["name"] == name else []
    for c in node.get("children", []):
        out.extend(find(c, name))
    return out

print(json.dumps({
    "compiles": [[c["attrs"]["program"], c["attrs"]["persistent_cache"], len(find(c, "exec.xla_compile"))]
                 for c in find(tree, "exec.compile")],
    "columnar_scans": metrics.COLUMNAR_SCANS.value - scans,
    "hits": metrics.XLA_PERSISTENT_CACHE_HITS.value, "misses": metrics.XLA_PERSISTENT_CACHE_MISSES.value,
    "groups": len(rows),
}))
"""


def run_child(cache_dir: str) -> dict:
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "PYTEST_"))}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1", TIDB_TPU_PALLAS="interpret", JAX_COMPILATION_CACHE_DIR=cache_dir,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    out = subprocess.run([sys.executable, "-c", CHILD, REPO], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    cache_dir = str(tmp_path_factory.mktemp("xla_cache"))
    first = run_child(cache_dir)
    written = sorted(os.listdir(cache_dir))
    second = run_child(cache_dir)
    return first, second, written, sorted(os.listdir(cache_dir))


def test_the_first_process_compiles_and_writes_its_programs(two_processes):
    first, _second, written, _after = two_processes
    assert first["columnar_scans"] == 2 and first["groups"] == 6
    assert first["compiles"] and all(verdict == "miss" and xla >= 1 for _p, verdict, xla in first["compiles"])
    assert any(name.startswith("jit_cop_") for name in written), written


def test_the_second_process_takes_every_program_from_the_cache(two_processes):
    first, second, _written, _after = two_processes
    assert [p for p, _v, _x in second["compiles"]] == [p for p, _v, _x in first["compiles"]]
    assert all(verdict == "hit" for _p, verdict, _x in second["compiles"]), second["compiles"]
    assert second["misses"] == 0 and second["hits"] >= len(second["compiles"])
    assert second["columnar_scans"] == 2 and second["groups"] == 6


def test_the_second_process_adds_no_entry_under_another_key(two_processes):
    _first, _second, written, after = two_processes
    programs = lambda names: [n for n in names if n.startswith("jit_cop_") and not n.endswith("-atime")]  # noqa: E731
    assert programs(after) == programs(written)
