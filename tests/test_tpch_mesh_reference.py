"""The `tpch_sf0p02_mesh4` deployment against its plain reference, in tier-1
(ISSUE 34): TPC-H Q1, Q6 and Q3 with drawn substitution parameters, served
over the wire from the row store of tables that the deployment's own `load`
cut into regions with `SPLIT TABLE` (lineitem 8, orders 4), on the suite's
eight host devices with `tidb_enable_tpu_mesh` and `tidb_allow_mpp` at their
defaults.  Every statement is one cross-chip program: the region lanes
sharded over the devices, the partial states merged on the device (psum for
Q6, all_gather and a merge-mode re-group for Q1 and Q3; the three end in
ORDER BY or have no GROUP BY, so the statement tier leaves them to the
per-request mesh tier).  Compared exactly with the numpy reference and with
the same statements under both sysvars OFF; one program a statement shape
whatever the draw; no fall-back; `cop-debug-raise` reaches the tier; the
spans a traced run is reduced by; the stacked lanes and Q3's build sides stay
on the devices from one statement to the next (PR 35); the one store's group
is the whole request, so the same program goes on through the root's half
behind its merge: one launch and one read-back a statement, no merge at the
root, the rows those of the split path (ISSUE 37).  The benchmark's cell `tpch_q1q6q3_mesh4`
makes the same comparison on four chips at 131,072 rows; here it is 4,096."""

import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from test_root_exec import root_half_left_off
from test_tpch_columnar_reference import BENCH, _json, _load, forget_root_programs

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.util import failpoint, metrics

CONFIG_DIR = os.path.join(BENCH, "configs", "tpch_sf0p02_mesh4")
ROWS = 4096
SEED = 2147483777   # one past 32 signed bits, as the driver's are
DRAWS = {
    "q1": [{"delta": d} for d in (90, 60, 120, 77)],
    "q6": [{"date": d, "discount": x, "quantity": q} for d, x, q in (
        ("1994-01-01", "0.06", 24), ("1993-01-01", "0.02", 25), ("1997-01-01", "0.09", 24), ("1995-01-01", "0.05", 25))],
    "q3": [{"segment": s, "date": d} for s, d in (
        ("BUILDING", "1995-03-15"), ("MACHINERY", "1995-03-01"), ("AUTOMOBILE", "1995-03-31"), ("HOUSEHOLD", "1995-03-09"))],
}
NAMES = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_LAUNCHES", "MESH_COP_BATCHES", "MESH_COP_LANES", "MPP_SELECTS",
         "MESH_COP_FALLBACKS", "MPP_FALLBACKS", "COP_FALLBACKS", "MESH_STACK_HITS", "MESH_STACK_MISSES", "COP_AUX_UPLOADS",
         "COP_DECODE_DEVICE_BYTES", "COP_DECODE_HITS", "COP_DECODE_MISSES", "PROGRAM_FETCHES", "ROOT_FUSED_STATEMENTS",
         "ROOT_FUSE_FALLBACKS")


class Served:
    def __init__(self):
        self.dep = _load(os.path.join(CONFIG_DIR, "deployment.py"), "tpch_sf0p02_mesh4_deployment")
        self.config = dict(_json(os.path.join(CONFIG_DIR, "config.json")), lineitem_rows=ROWS)
        self.sql = _json(os.path.join(CONFIG_DIR, "statements.json"))
        self.mix = _json(os.path.join(BENCH, "traffic", "q1q6q3_params.json"))
        self.data = self.dep.generate(self.config, SEED)
        self.srv = MySQLServer(port=0)
        self.srv.start_background()
        self.conn = MiniClient(self.srv.host, self.srv.port, timeout=600.0)
        self.lines = []
        self.dep.load(self.conn, self.data, self.config, lambda **line: self.lines.append(line))
        self.conn.query(f"set tidb_isolation_read_engines = '{self.mix['read_engines']}'")
        forget_root_programs()   # the first executions below are counted as a fresh server's
        self.cases = {(name, i): self.run(name, p) for name, draws in DRAWS.items() for i, p in enumerate(draws)}
        with root_half_left_off():   # the mesh tier as it was: the merged state read back, the root's merge a second program
            self.split = {(name, i): self.run(name, p) for name, draws in DRAWS.items() for i, p in enumerate(draws)}
        for var in ("tidb_enable_tpu_mesh", "tidb_allow_mpp"):
            self.conn.query(f"set {var} = OFF")
        self.single = {(name, i): self.run(name, p) for name, draws in DRAWS.items() for i, p in enumerate(draws)}
        for var in ("tidb_enable_tpu_mesh", "tidb_allow_mpp"):
            self.conn.query(f"set {var} = ON")

    def run(self, name: str, params: dict, trace: bool = False) -> dict:
        before = {n: getattr(metrics, n).value for n in NAMES}
        _, rows = self.conn.query(("trace format='json' " if trace else "") + self.sql[name].format(**params))
        return {"params": params, "rows": rows, "moved": {n: getattr(metrics, n).value - before[n] for n in NAMES}}

    def close(self):
        self.conn.close()
        self.srv.close()


@pytest.fixture(scope="module")
def served():
    s = Served()
    yield s
    s.close()


def find(node: dict, name: str) -> list:
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]


CASES = [(name, i) for name in DRAWS for i in range(4)]


def test_load_cut_the_tables_as_the_configuration_says(served):
    (line,) = [ln for ln in served.lines if ln.get("phase") == "split"]
    assert line["regions"] == {"customer": 1, "orders": 4, "lineitem": 8}
    assert len(served.srv.store.cluster.regions()) == 13   # what lies before customer's records, then 1 + 4 + 8


@pytest.mark.parametrize("name,i", CASES)
def test_served_answer_equals_the_plain_reference_and_the_one_device_answer(served, name, i):
    got = served.cases[name, i]
    want = served.dep.reference(name, got["params"], served.data)
    assert served.dep.mismatch(name, want, got["rows"]) is None, (got["params"], got["rows"])
    assert got["rows"] == served.single[name, i]["rows"]


@pytest.mark.parametrize("name,i", CASES)
def test_every_statement_is_one_cross_chip_program_and_none_falls_back(served, name, i):
    m = served.cases[name, i]["moved"]
    assert m["MESH_COP_BATCHES"] + m["MPP_SELECTS"] == 1 and m["MESH_COP_LANES"] == 8, m
    assert m["MESH_COP_FALLBACKS"] == m["MPP_FALLBACKS"] == m["COP_FALLBACKS"] == 0, m
    off = served.single[name, i]["moved"]
    assert off["MESH_COP_BATCHES"] == off["MPP_SELECTS"] == off["MESH_COP_LANES"] == 0, off


@pytest.mark.parametrize("name", list(DRAWS))
def test_the_first_draw_builds_the_programs_and_no_later_draw_builds_one(served, name):
    first = served.cases[name, 0]["moved"]
    assert first["PROGRAM_COMPILES"] >= 1 and first["XLA_COMPILES"] >= 1, first   # the statement's one mesh program
    for i in (1, 2, 3):
        m = served.cases[name, i]["moved"]
        assert m["PROGRAM_COMPILES"] == m["XLA_COMPILES"] == 0, (name, i, m)
        assert m["PROGRAM_LAUNCHES"] == m["PROGRAM_FETCHES"] == 1, (name, i, m)   # Q3's build scans are cop results


@pytest.mark.parametrize("name,i", CASES)
def test_the_mesh_program_finishes_the_statement_and_answers_the_split_paths_rows(served, name, i):
    """The root's half ran behind the on-device merge: no second program.
    With it left off, the same mesh launch hands back one merged state and
    the root merges it (two launches, two read-backs): the same rows in the
    same order, Q3's ten included."""
    fused, split = served.cases[name, i], served.split[name, i]
    assert fused["rows"] == split["rows"]
    m = fused["moved"]
    assert (m["ROOT_FUSED_STATEMENTS"], m["ROOT_FUSE_FALLBACKS"], m["MESH_COP_BATCHES"]) == (1, 0, 1), m
    m = split["moved"]
    assert (m["ROOT_FUSED_STATEMENTS"], m["ROOT_FUSE_FALLBACKS"], m["MESH_COP_BATCHES"]) == (0, 1, 1), m
    assert m["PROGRAM_LAUNCHES"] == m["PROGRAM_FETCHES"] == 2 and m["MESH_COP_FALLBACKS"] == 0, m
    assert m["PROGRAM_COMPILES"] == (0 if i else 2), m       # the plain mesh program and the root's merge, once a shape


@pytest.mark.parametrize("name", list(DRAWS))
def test_every_draw_after_the_first_finds_the_lanes_stacked_and_the_build_sides_uploaded(served, name):
    """The three shapes read the same columns and ranges of `lineitem`, so
    one resident batch serves them all: the module's first statement (Q1's
    first draw) stacks it, every later one finds it on the devices."""
    for i in range(4):
        m = served.cases[name, i]["moved"]
        first = (name, i) == ("q1", 0)
        assert (m["MESH_STACK_HITS"], m["MESH_STACK_MISSES"]) == ((0, 1) if first else (1, 0)), (name, i, m)
        assert (m["COP_DECODE_DEVICE_BYTES"] > 0) == (first or (name, i) == ("q3", 0)), (name, i, m)   # Q3's first: its build scans
        assert m["COP_AUX_UPLOADS"] == (2 if (name, i) == ("q3", 0) else 0), (name, i, m)
        assert m["COP_DECODE_HITS"] >= 8 or first, (name, i, m)      # the lanes' chunks, still looked up for read flow
        off = served.single[name, i]["moved"]
        assert off["MESH_STACK_HITS"] == off["MESH_STACK_MISSES"] == 0, off


def test_the_resident_batch_is_sharded_over_the_region_axis_and_the_build_sides_are_replicated(served):
    store = served.srv.store
    with store._cop_lock:
        stacks = [(k, v, cost) for k, (v, _ts, cost) in store._batch_cache._entries.items() if k[1][0] == "mesh.stack"]
    ((key, batch, cost),) = stacks
    _tag, cap, r_pad, devices, lanes = key[1]
    assert (len(lanes), cap, r_pad, devices) == (8, 512, 8, 8) and cost == batch.nbytes()
    assert served.cases["q1", 0]["moved"]["COP_DECODE_DEVICE_BYTES"] == batch.nbytes()     # once, not per statement
    leaves = jax.tree_util.tree_leaves(batch)
    assert len(leaves) == 16 * 2 + 5 + 2     # lineitem's sixteen columns, five of them strings, row_valid, n_rows
    for leaf in leaves:
        assert isinstance(leaf.sharding, NamedSharding) and leaf.sharding.spec == PartitionSpec("region")
        assert leaf.sharding.mesh.devices.tolist() == jax.devices() and leaf.shape[0] == 8
        assert [s.data.shape[0] for s in leaf.addressable_shards] == [1] * 8          # R_pad / D lanes a device
    assert np.asarray(batch.n_rows).tolist() == [ROWS // 8] * 8
    with store._aux_lock:
        mesh_sides = {k: v for k, v in store._aux_batch_cache.items() if isinstance(k, tuple)}
        merged = list(store._build_side_cache.values())
    assert sorted(k[1] for k in mesh_sides) == [8, 8]                 # orders and customer, for a launch over eight devices
    ((parts, orders),) = merged                                       # orders' four region chunks, concatenated once
    assert len(parts) == 4 and orders.num_rows() == len(served.data["orders"]["orderkey"])
    assert any(chunk is orders for chunk, _b in mesh_sides.values())
    for _chunk, side in mesh_sides.values():
        for leaf in jax.tree_util.tree_leaves(side):
            assert isinstance(leaf.sharding, NamedSharding) and leaf.sharding.is_fully_replicated
            assert leaf.devices() == set(jax.devices())


def test_a_traced_q3_lays_the_mesh_tier_under_the_dispatch_span(served):
    got = served.run("q3", {"segment": "FURNITURE", "date": "1995-03-20"}, trace=True)
    tree = json.loads(got["rows"][0][0])
    roots = find(tree, "distsql.execute_root")
    (probe,) = [r for r in roots if find(r, "cop.mesh_execute")]
    (stack,) = find(probe, "mesh.stack")
    assert stack["attrs"]["lanes"] == 8 and stack["attrs"]["devices"] == 8
    assert stack["attrs"]["rows"] == ROWS and stack["attrs"]["bytes"] > 0 and stack["attrs"]["hit"] is True
    orders, customer = len(served.data["orders"]["orderkey"]), len(served.data["customer"]["custkey"])
    # `orders` comes in four regions: their concatenation is the object that was uploaded
    assert [(a["attrs"]["rows"], a["attrs"]["hit"]) for a in find(probe, "cop.aux_batch")] == [(orders, True), (customer, True)]
    assert (got["moved"]["MESH_STACK_HITS"], got["moved"]["MESH_STACK_MISSES"], got["moved"]["COP_AUX_UPLOADS"]) == (1, 0, 0)
    (execute,) = find(probe, "cop.mesh_execute")
    assert find(execute, "mesh.stack") == [stack] and find(execute, "exec.launch") and not find(tree, "exec.compile")
    assert [n["attrs"]["program"] for n in find(execute, "exec.launch")] == ["cop_scan_sel_join_join_groupagg_topn_m8x8"]
    assert execute["attrs"]["root_fused"] is True and len(find(probe, "exec.launch")) == 1
    assert find(probe, "cop.mesh_decode") and not find(tree, "distsql.root_merge")
    assert got["moved"]["MESH_COP_BATCHES"] == 1 and got["moved"]["PROGRAM_COMPILES"] == 0


def test_cop_debug_raise_reaches_the_mesh_tier(served, monkeypatch):
    """Unarmed, a failed launch degrades to the tier below and is counted;
    armed (as the benchmark arms it for a whole run) it fails the
    statement, so a four-chip cell cannot be measured on one chip."""
    from tidb_tpu.store import store as store_mod

    def broken(*_a, **_k):
        raise RuntimeError("injected mesh launch failure")

    served.srv.store.evict_caches()     # the lanes are resident by now, and a hit stacks nothing
    monkeypatch.setattr(store_mod, "to_stacked_device_batch", broken)
    p = {"date": "1996-01-01", "discount": "0.03", "quantity": 24}
    got = served.run("q6", p)
    assert served.dep.mismatch("q6", served.dep.reference("q6", p, served.data), got["rows"]) is None
    assert got["moved"]["MESH_COP_FALLBACKS"] == 1 and got["moved"]["MESH_COP_BATCHES"] == 0, got["moved"]
    failpoint.enable("cop-debug-raise")
    try:
        with pytest.raises(Exception, match="injected mesh launch failure"):
            served.run("q6", dict(p, discount="0.04"))   # another literal: the lanes above left cop results behind
    finally:
        failpoint.disable("cop-debug-raise")
    monkeypatch.undo()
    # armed, a sound statement is served as before, and the size decline stays a counted decline
    failpoint.enable("cop-debug-raise")
    try:
        assert served.run("q6", dict(p, discount="0.07"))["moved"]["MESH_COP_BATCHES"] == 1
        served.conn.query("set tidb_tpu_mesh_min_rows = 100000000")
        m = served.run("q6", dict(p, discount="0.08"))["moved"]
        assert m["MESH_COP_BATCHES"] == 0 and m["COP_FALLBACKS"] == 0
    finally:
        failpoint.disable("cop-debug-raise")
        served.conn.query("set tidb_tpu_mesh_min_rows = 0")   # `= default` leaves the value where it is


def test_an_insert_into_lineitem_makes_the_next_statement_miss_once_and_the_answer_follows_the_data(served):
    """Last in the file: it writes to `lineitem` (and takes the row out
    again).  The write drops the stacked batch with the other version
    caches; the next Q3 stacks the lanes of the new data and
    files them, the one after finds them."""
    p = {"segment": "BUILDING", "date": "1995-03-15"}
    want = served.dep.reference("q3", p, served.data)
    warm = served.run("q3", p)
    assert served.dep.mismatch("q3", want, warm["rows"]) is None
    lead = max(want, key=lambda k: want[k][0])
    o, l = served.data["orders"], served.data["lineitem"]
    line = {"oidx": int(np.flatnonzero(o["orderkey"] == lead)[0]), "extendedprice": 9_999_999_00, "discount": 5,
            "shipdate": int((np.datetime64("1998-01-01") - served.dep.EPOCH).astype(int))}
    served.conn.query(
        f"insert into lineitem values ({lead}, 1, 1, 8, 1.00, 9999999.00, 0.05, 0.00, 'N', 'O', "
        "'1998-01-01', '1998-01-01', '1998-01-02', 'NONE', 'AIR', 'the mesh tier keeps what it stacked')")
    try:
        grown = dict(served.data, lineitem={k: np.append(v, line[k]) if k in line else v for k, v in l.items()})
        want_new = served.dep.reference("q3", p, grown)
        assert want_new[lead][0] > want[lead][0]
        first, traced, third = served.run("q3", p), served.run("q3", p, trace=True), served.run("q3", dict(p, date="1995-03-16"))
        for got in (first, third):
            assert got["moved"]["MESH_COP_BATCHES"] == 1 and got["moved"]["MESH_COP_FALLBACKS"] == 0, got["moved"]
        assert (first["moved"]["MESH_STACK_HITS"], first["moved"]["MESH_STACK_MISSES"]) == (0, 1)
        assert first["moved"]["COP_AUX_UPLOADS"] == 2            # the write dropped the build scans' cop results too
        assert served.dep.mismatch("q3", want_new, first["rows"]) is None
        assert served.dep.mismatch("q3", want, first["rows"]) is not None
        (stack,) = find(json.loads(traced["rows"][0][0]), "mesh.stack")
        assert stack["attrs"]["hit"] is True and stack["attrs"]["rows"] == ROWS + 1
        for got in (traced, third):
            m = got["moved"]
            assert (m["MESH_STACK_HITS"], m["MESH_STACK_MISSES"], m["COP_AUX_UPLOADS"]) == (1, 0, 0), m
        assert served.dep.mismatch(
            "q3", served.dep.reference("q3", dict(p, date="1995-03-16"), grown), third["rows"]) is None
        for var in ("tidb_enable_tpu_mesh", "tidb_allow_mpp"):
            served.conn.query(f"set {var} = OFF")
        assert served.run("q3", p)["rows"] == first["rows"]
        for var in ("tidb_enable_tpu_mesh", "tidb_allow_mpp"):
            served.conn.query(f"set {var} = ON")
    finally:
        served.conn.query(f"delete from lineitem where l_orderkey = {lead} and l_linenumber = 8")
    back = served.run("q3", p)
    assert served.dep.mismatch("q3", want, back["rows"]) is None
    assert (back["moved"]["MESH_STACK_HITS"], back["moved"]["MESH_STACK_MISSES"]) == (0, 1)
