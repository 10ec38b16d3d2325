"""The exchange tier under the one rule for what a compiled program depends
on (ISSUE 34): the shard_map programs of `mpp/exchange_op.py` and
`parallel/grouped.py` are built from the plan's shape, kept in the store's
`ProgramCache` under `dag.program_key()` and called with
`dag.program_operands()`, so a string, a date and a decimal literal are
operands and a fresh draw builds nothing; a NULL and a non-ASCII string
stay in the key.  The tier is chosen inside `distsql.execute_root`, so its
spans lie under the dispatch layer's; `cop-debug-raise` reaches it.  On
the suite's eight host devices, over a table cut with `SPLIT TABLE`;
counts, spans and answers only."""

import json

import pytest

from tidb_tpu.sql.session import Session
from tidb_tpu.util import failpoint, metrics

NAMES = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_LAUNCHES", "PROGRAM_CACHE_HITS", "PROGRAM_PARAMS_BOUND",
         "PROGRAM_STR_PARAMS_BOUND", "MPP_SELECTS", "MPP_FALLBACKS", "MESH_COP_BATCHES")
AGG = "select g, count(*), sum(v) from f where s = '{s}' and d < date '{d}' and p > {p} group by g"
JOIN = ("select f.g, count(*), sum(f.v) from f, dim where f.g = dim.k and dim.seg = '{s}' "
        "and f.d < date '{d}' and f.p > {p} group by f.g")
DRAWS = {
    "agg": (AGG, [dict(s="abc", d="1995-03-15", p="12.50"), dict(s="xyz", d="1995-03-20", p="3.25"),
                  dict(s="abd", d="1995-03-02", p="40.00"), dict(s="q", d="1995-03-28", p="0.75")]),
    "join": (JOIN, [dict(s="AUTO", d="1995-03-15", p="12.50"), dict(s="MACH", d="1995-03-20", p="3.25"),
                    dict(s="BUILD", d="1995-03-02", p="40.00"), dict(s="FURN", d="1995-03-28", p="0.75")]),
}


class Served:
    def __init__(self):
        s = self.sess = Session()
        s.execute("create table f (id bigint primary key, g bigint, s varchar(12), d date, p decimal(10,2), v bigint)")
        s.execute("create table dim (k bigint primary key, seg varchar(10))")
        for lo in range(1, 2001, 500):
            s.execute("insert into f values " + ",".join(
                f"({i},{i % 37},'{('abc', 'abd', 'xyz', 'q')[i % 4]}','1995-03-{1 + i % 28:02d}',{i % 50}.{i % 100:02d},{i % 11})"
                for i in range(lo, lo + 500)))
        s.execute("insert into dim values " + ",".join(f"({k},'{('AUTO', 'BUILD', 'FURN', 'MACH')[k % 4]}')" for k in range(37)))
        assert s.execute("split table f between (1) and (2001) regions 8").values() == [[8, 1.0]]
        self.cases = {(kind, i): self.run(sql.format(**p)) for kind, (sql, draws) in DRAWS.items() for i, p in enumerate(draws)}
        for var in ("tidb_enable_tpu_mesh", "tidb_allow_mpp"):
            s.execute(f"set {var} = OFF")
        self.single = {(kind, i): self.run(sql.format(**p)) for kind, (sql, draws) in DRAWS.items() for i, p in enumerate(draws)}
        for var in ("tidb_enable_tpu_mesh", "tidb_allow_mpp"):
            s.execute(f"set {var} = ON")

    def run(self, sql: str) -> dict:
        before = {n: getattr(metrics, n).value for n in NAMES}
        rows = sorted(map(str, self.sess.execute(sql).values()))
        return {"rows": rows, "moved": {n: getattr(metrics, n).value - before[n] for n in NAMES}}

    def trace(self, sql: str) -> dict:
        return json.loads(self.sess.execute("trace format='json' " + sql).rows[0][0].val)


@pytest.fixture(scope="module")
def served():
    return Served()


def find(node: dict, name: str) -> list:
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]


@pytest.mark.parametrize("kind", list(DRAWS))
def test_the_first_draw_builds_the_exchange_program_and_no_later_draw_builds_one(served, kind):
    first = served.cases[kind, 0]["moved"]
    assert first["MPP_SELECTS"] == 1 and first["PROGRAM_COMPILES"] >= 1 and first["XLA_COMPILES"] >= 1, first
    for i in (1, 2, 3):
        m = served.cases[kind, i]["moved"]
        assert m["MPP_SELECTS"] == 1 and m["MPP_FALLBACKS"] == 0 and m["MESH_COP_BATCHES"] == 0, (kind, i, m)
        assert m["PROGRAM_COMPILES"] == m["XLA_COMPILES"] == 0, (kind, i, m)   # fails where the program is keyed by its literals
        assert m["PROGRAM_LAUNCHES"] == 1 and m["PROGRAM_CACHE_HITS"] == 1, (kind, i, m)
        # the string, the date and the decimal of the statement, handed over as operands
        assert m["PROGRAM_STR_PARAMS_BOUND"] == 1 and m["PROGRAM_PARAMS_BOUND"] == 3, (kind, i, m)


@pytest.mark.parametrize("kind,i", [(k, i) for k in DRAWS for i in range(4)])
def test_operands_answer_what_the_one_device_path_answers(served, kind, i):
    assert served.cases[kind, i]["rows"] == served.single[kind, i]["rows"]
    assert served.single[kind, i]["moved"]["MPP_SELECTS"] == 0
    assert any(len(served.cases[kind, j]["rows"]) > 0 for j in range(4))


def test_a_non_ascii_string_and_a_null_stay_in_the_key(served):
    """What the trace reads stays baked: the CI compares screen a constant's
    bytes while they trace, a NULL shapes the expression around it."""
    ascii_again = served.run(AGG.format(s="zzz", d="1995-03-09", p="1.00"))["moved"]
    assert ascii_again["PROGRAM_COMPILES"] == 0 and ascii_again["PROGRAM_STR_PARAMS_BOUND"] == 1
    accents = served.run(AGG.format(s="été", d="1995-03-09", p="1.00"))["moved"]
    assert accents["MPP_SELECTS"] == 1 and accents["PROGRAM_COMPILES"] == 1 and accents["PROGRAM_STR_PARAMS_BOUND"] == 0
    more = served.run(AGG.format(s="été", d="1995-03-19", p="2.00"))["moved"]   # the same bytes: the same program
    assert more["PROGRAM_COMPILES"] == 0 and more["PROGRAM_PARAMS_BOUND"] == 2
    with_null = AGG.replace(" group by", " and coalesce(null, v) > {n} group by")
    with_two = AGG.replace(" group by", " and coalesce(2, v) > {n} group by")
    p = dict(s="abc", d="1995-03-15", p="12.50")
    assert served.run(with_null.format(n=3, **p))["moved"]["PROGRAM_COMPILES"] == 1
    assert served.run(with_null.format(n=4, **p))["moved"]["PROGRAM_COMPILES"] == 0
    assert served.run(with_two.format(n=3, **p))["moved"]["PROGRAM_COMPILES"] == 1   # a value where the NULL was: another shape


def test_the_exchange_programs_live_in_the_stores_program_cache(served):
    keys = [k for k in served.sess.store.programs._cache if k[0] in ("mpp_exchange_join_agg", "mesh_exchange_group_agg")]
    assert {k[0] for k in keys} == {"mpp_exchange_join_agg", "mesh_exchange_group_agg"}
    for k in keys:
        assert k[1] == k[1] and isinstance(k[2], tuple) and len(k[2]) == 8   # program key, then the mesh's devices
    from tidb_tpu.mpp import dispatch, exchange_op

    assert not hasattr(exchange_op, "_PROGRAM_CACHE")
    # the ladder's rung is remembered under the same key, so a draw starts where the last one ended
    assert all(isinstance(k[0], tuple) for k in dispatch._LADDER_HINTS)
    n = len(dispatch._LADDER_HINTS)
    served.run(JOIN.format(s="AUTO", d="1995-03-05", p="7.00"))
    assert len(dispatch._LADDER_HINTS) == n


def test_a_traced_join_lays_the_exchange_tier_under_the_dispatch_span(served):
    tree = served.trace(JOIN.format(s="FURN", d="1995-03-11", p="1.00"))
    (root,) = [r for r in find(tree, "distsql.execute_root") if find(r, "mpp.dispatch")]
    (dispatch_span,) = find(root, "mpp.dispatch")
    assert [c["name"] for c in dispatch_span["children"]] == ["mpp.scan", "mesh.stack", "mpp.exchange"]
    scan, stack, exchange = dispatch_span["children"]
    assert len(find(scan, "distsql.cop_task")) == 8
    assert (stack["attrs"]["lanes"], stack["attrs"]["devices"], stack["attrs"]["rows"]) == (8, 8, 2000) and stack["attrs"]["bytes"] > 0
    assert exchange["attrs"]["kind"] == "join" and exchange["attrs"]["retries"] == 0 and len(exchange["attrs"]["rung"]) == 2
    assert [c["name"] for c in exchange["children"]] == ["exec.program", "exec.launch", "exec.wait", "exec.readback"]
    assert exchange["children"][0]["attrs"] == {"cache_hit": True}
    assert exchange["children"][1]["attrs"] == {"program": "mpp_exchange_join_agg", "params": 3}
    # no span of the tier lies beside the dispatch layer's: the harness splits an operation there
    assert not [n for r in find(tree, "session.execute") for n in r["children"] if n["name"].startswith("mpp.")]


@pytest.mark.parametrize("where", ["run_exchange_join_agg", "exchange_lanes"])
def test_cop_debug_raise_reaches_the_exchange_tier(served, monkeypatch, where):
    """Unarmed, a failure inside the tier is a counted fall-back onto the
    tiers below (degrade, never fail); armed it fails the statement."""
    from tidb_tpu.mpp import exchange_op
    from tidb_tpu.store.store import TPUStore

    def broken(*_a, **_k):
        raise RuntimeError("injected exchange failure")

    monkeypatch.setattr(*((exchange_op, "run_exchange_join_agg") if where == "run_exchange_join_agg"
                          else (TPUStore, "exchange_lanes")), broken)
    sql = JOIN.format(s="MACH", d="1995-03-17", p="2.50")
    got = served.run(sql)
    assert got["moved"]["MPP_FALLBACKS"] == 1 and got["moved"]["MPP_SELECTS"] == 0
    failpoint.enable("cop-debug-raise")
    try:
        with pytest.raises(Exception, match="injected exchange failure"):
            served.run(sql)
        monkeypatch.undo()
        sound = served.run(sql)
        assert sound["moved"]["MPP_SELECTS"] == 1 and sound["rows"] == got["rows"]
        # a value wider than the exchange carries, in a column the statement
        # never reads, decides nothing (ISSUE 38: TPC-H's l_comment beside Q18)
        served.sess.execute(f"insert into f values (5001, 1, '{'w' * 12}', '1995-03-01', 1.00, 1)")
        served.sess.execute("alter table f modify column s varchar(64)")
        served.sess.execute(f"insert into f values (5002, 1, '{'w' * 40}', '1995-03-01', 1.00, 1)")
        unread = served.run(sql)["moved"]
        assert unread["MPP_FALLBACKS"] == 0 and unread["MPP_SELECTS"] == 1
        # in one it reads, an eligibility decline stays a counted decline
        served.sess.execute("alter table dim modify column seg varchar(64)")
        served.sess.execute(f"insert into dim values (5003, '{'w' * 40}')")
        wide = served.run(sql)["moved"]
        assert wide["MPP_FALLBACKS"] == 1 and wide["MPP_SELECTS"] == 0
    finally:
        failpoint.disable("cop-debug-raise")
        served.sess.execute("delete from f where id > 5000")
        served.sess.execute("delete from dim where k > 5000")
