"""MPP exchange data plane (ISSUE 18): fragment planner eligibility,
fragment-topology wire round-trip, dispatch tier fall-out (failpoints,
epoch retries), the non-unique radix build parity pin, and the
tidb_tpu_mpp_* metric families."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.exec import Aggregation, ColumnInfo, DAGRequest, Join, Selection, TableScan
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.mpp.fragment import (
    EXCHANGE_HASH,
    EXCHANGE_PASSTHROUGH,
    ROOT_COLLECTOR,
    chunks_exchange_safe,
    fragment_plan,
)
from tidb_tpu.types import Datum, new_longlong, new_varchar
from tidb_tpu.util import failpoint
from tidb_tpu.util import metrics as M

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

I = new_longlong()


def _scan(tid):
    return TableScan(tid, (ColumnInfo(1, I), ColumnInfo(2, I)))


def _chain_dag(n_joins=2):
    """scan [Join(scan)]*n Aggregation(GROUP BY) — the Q3 chain shape."""
    exs = [_scan(10)]
    for j in range(n_joins):
        exs.append(Join(build=(_scan(11 + j),), probe_keys=(col(0, I),),
                        build_keys=(col(0, I),), join_type="inner"))
    exs.append(Aggregation(group_by=(col(1, I),),
                           aggs=(AggDesc("count", ()),)))
    return DAGRequest(tuple(exs), output_offsets=(0, 1))


class TestFragmentPlanner:
    def test_q3_chain_cuts_into_exchange_linked_fragments(self):
        fp = fragment_plan(_chain_dag(2), n_tasks=8)
        assert fp is not None and fp.n_tasks == 8
        # probe, 2 builds, 2 joins, final = 6 fragments
        assert len(fp.fragments) == 6
        probe = fp.fragments[0]
        assert probe.sender.exchange_type == EXCHANGE_HASH
        assert probe.sender.target_fragment == 2
        # every join fragment receives probe side first, build second
        join0 = fp.fragments[2]
        assert [r.source_fragment for r in join0.receivers] == [0, 1]
        join1 = fp.fragments[4]
        assert [r.source_fragment for r in join1.receivers] == [2, 3]
        final = fp.fragments[fp.root]
        assert final.sender.exchange_type == EXCHANGE_PASSTHROUGH
        assert final.sender.target_fragment == ROOT_COLLECTOR
        # the last join fragment re-exchanges by the GROUP key to final
        assert join1.sender.target_fragment == fp.root
        assert join1.sender.exchange_type == EXCHANGE_HASH

    def test_agg_shape_is_two_fragments(self):
        dag = DAGRequest(
            (_scan(10), Selection((func("gt", I, col(1, I), lit(2, I)),)),
             Aggregation(group_by=(col(0, I),), aggs=(AggDesc("count", ()),))),
            output_offsets=(0, 1))
        fp = fragment_plan(dag, n_tasks=4)
        assert fp is not None and len(fp.fragments) == 2
        assert fp.fragments[0].sender.exchange_type == EXCHANGE_HASH
        assert fp.fragments[1].sender.target_fragment == ROOT_COLLECTOR

    def test_join_inside_build_side_stays_off_mesh(self):
        inner = Join(build=(_scan(12),), probe_keys=(col(0, I),),
                     build_keys=(col(0, I),), join_type="inner")
        dag = DAGRequest(
            (_scan(10),
             Join(build=(_scan(11), inner), probe_keys=(col(0, I),),
                  build_keys=(col(0, I),), join_type="inner"),
             Aggregation(group_by=(col(1, I),), aggs=(AggDesc("count", ()),))),
            output_offsets=(0, 1))
        assert fragment_plan(dag, n_tasks=4) is None

    def test_scalar_agg_has_no_group_key_to_exchange(self):
        dag = DAGRequest(
            (_scan(10), Aggregation(group_by=(), aggs=(AggDesc("count", ()),))),
            output_offsets=(0,))
        assert fragment_plan(dag, n_tasks=4) is None

    def test_string_width_gate_measures_actual_bytes(self):
        from tidb_tpu.chunk import Chunk

        V = new_varchar(64)
        ok = Chunk.from_rows([V], [[Datum.string("x" * 32)]])
        wide = Chunk.from_rows([V], [[Datum.string("y" * 33)]])
        assert chunks_exchange_safe([ok])
        assert not chunks_exchange_safe([wide])


class TestFragmentWire:
    def test_topology_round_trips_byte_exactly(self):
        from tidb_tpu.codec.wire import decode_fragment_plan, encode_fragment_plan

        for dag in (_chain_dag(1), _chain_dag(3)):
            fp = fragment_plan(dag, n_tasks=8)
            raw = encode_fragment_plan(fp)
            fp2 = decode_fragment_plan(raw)
            # the decoded topology re-encodes to the SAME bytes (stable
            # numbering) and matches structurally
            assert encode_fragment_plan(fp2) == raw
            assert fp2.n_tasks == fp.n_tasks and fp2.root == fp.root
            assert len(fp2.fragments) == len(fp.fragments)
            for a, b in zip(fp.fragments, fp2.fragments):
                assert a.idx == b.idx
                assert a.sender.exchange_type == b.sender.exchange_type
                assert a.sender.target_fragment == b.sender.target_fragment
                assert len(a.sender.partition_keys) == len(b.sender.partition_keys)
                assert [r.source_fragment for r in a.receivers] == \
                       [r.source_fragment for r in b.receivers]
                assert len(a.executors) == len(b.executors)


def _q3_session(nl=600, no=40, nc=12):
    from tidb_tpu.sql import Session

    s = Session()
    s.execute("create table cust (c_id bigint primary key, seg varchar(2))")
    s.execute("insert into cust values " + ",".join(
        f"({i}, '{'AB'[i % 2]}')" for i in range(nc)))
    s.execute("create table ords (o_id bigint primary key, ckey bigint, odate bigint)")
    s.execute("insert into ords values " + ",".join(
        f"({i}, {i % nc}, {1000 + i % 9})" for i in range(no)))
    s.execute("create table items (i_id bigint primary key, oid bigint, v decimal(10,2))")
    s.execute("insert into items values " + ",".join(
        f"({i}, {(i * 3) % (no + 4)}, {i}.25)" for i in range(nl)))
    return s


Q3_SQL = ("select oid, count(*), sum(v) from items "
          "join ords on oid = o_id join cust on ckey = c_id "
          "where seg = 'B' and odate < 1007 group by oid")


def _canon(rows):
    return sorted(
        tuple(None if d.is_null() else str(d.val) for d in r) for r in rows)


class TestMppDispatch:
    def test_q3_chain_rides_mpp_byte_identical(self):
        s = _q3_session()
        m0, f0 = M.MPP_SELECTS.value, M.MPP_FRAGMENTS.value
        b0 = M.MPP_EXCHANGED_BYTES.value
        mpp_rows = s.execute(Q3_SQL).rows
        assert M.MPP_SELECTS.value == m0 + 1, "Q3 chain did not ride mpp"
        assert M.MPP_FRAGMENTS.value - f0 >= 2, "chain must plan >= 2 fragments"
        assert M.MPP_EXCHANGED_BYTES.value > b0
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(mpp_rows) == _canon(s.execute(Q3_SQL).rows)

    def test_allow_mpp_off_is_served_by_execute_root(self):
        """The sysvar selects no second exchange implementation: OFF sends
        the statement to execute_root, and no exchange program runs."""
        s = _q3_session()
        s.execute("set tidb_allow_mpp = OFF")
        m0, e0 = M.MPP_SELECTS.value, M.MESH_SELECTS.value
        rows = s.execute(Q3_SQL).rows
        assert M.MPP_SELECTS.value == m0
        assert M.MESH_SELECTS.value == e0
        tree = s.execute("TRACE FORMAT='json' " + Q3_SQL).values()[0][0]
        assert "distsql.execute_root" in tree and "mpp.dispatch" not in tree
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(rows) == _canon(s.execute(Q3_SQL).rows)

    def test_dispatch_lost_is_a_counted_fallback(self):
        s = _q3_session()
        m0, fb0 = M.MPP_SELECTS.value, M.MPP_FALLBACKS.value
        with failpoint.enabled("mpp/dispatch-lost"):
            rows = s.execute(Q3_SQL).rows
        assert M.MPP_SELECTS.value == m0
        assert M.MPP_FALLBACKS.value == fb0 + 1
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(rows) == _canon(s.execute(Q3_SQL).rows)

    def test_exchange_stall_is_a_counted_fallback(self):
        s = _q3_session()
        m0, fb0 = M.MPP_SELECTS.value, M.MPP_FALLBACKS.value
        with failpoint.enabled("mpp/exchange-stall"):
            rows = s.execute(Q3_SQL).rows
        assert M.MPP_SELECTS.value == m0
        assert M.MPP_FALLBACKS.value == fb0 + 1
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(rows) == _canon(s.execute(Q3_SQL).rows)

    def test_mid_query_epoch_error_retries_typed(self):
        """A region-epoch error inside the mpp probe scan rides the same
        transparent re-split retry as the per-region path — typed region
        fall-out, never a torn result."""
        s = _q3_session()
        r0, m0 = M.DISTSQL_RETRIES.value, M.MPP_SELECTS.value
        with failpoint.enabled("cop-region-error", 1):
            rows = s.execute(Q3_SQL).rows
        assert M.DISTSQL_RETRIES.value == r0 + 1
        assert M.MPP_SELECTS.value == m0 + 1
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(rows) == _canon(s.execute(Q3_SQL).rows)

    def test_partitioned_probe_table_rides_mpp(self):
        from tidb_tpu.sql import Session

        s = Session()
        s.execute("create table pd (d_id bigint primary key, g bigint)")
        s.execute("insert into pd values " + ",".join(
            f"({i}, {i % 5})" for i in range(20)))
        s.execute("CREATE TABLE pt (a BIGINT PRIMARY KEY, g BIGINT, v BIGINT) "
                  "PARTITION BY HASH(a) PARTITIONS 3")
        s.execute("insert into pt values " + ",".join(
            f"({i}, {i % 5}, {i * 7 % 23})" for i in range(300)))
        sql = ("select pt.g, count(*), sum(v) from pt "
               "join pd on pt.g = d_id group by pt.g")
        m0 = M.MPP_SELECTS.value
        rows = s.execute(sql).rows
        assert M.MPP_SELECTS.value == m0 + 1, "partitioned probe did not ride mpp"
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(rows) == _canon(s.execute(sql).rows)

    def test_replica_served_probe_matches_row_store(self):
        s = _q3_session()
        s.execute("ALTER TABLE items SET COLUMNAR REPLICA 1")
        s.store.pd.tick()
        m0 = M.MPP_SELECTS.value
        rows = s.execute(Q3_SQL).rows
        assert M.MPP_SELECTS.value == m0 + 1
        r = s.execute("TRACE " + Q3_SQL).values()
        assert any("mpp.dispatch" in str(row[0]) for row in r)
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(rows) == _canon(s.execute(Q3_SQL).rows)

    def test_mpp_metric_families_pass_scrape_check(self):
        s = _q3_session()
        s.execute(Q3_SQL)
        text = M.REGISTRY.dump()
        for family in (
            "tidb_tpu_mpp_selects_total",
            "tidb_tpu_mpp_fragments_total",
            "tidb_tpu_mpp_tasks_total",
            "tidb_tpu_mpp_fallbacks_total",
            "tidb_tpu_mpp_exchanged_bytes_total",
        ):
            assert f"# TYPE {family}" in text, family
        from scrape_check import validate

        assert validate(text) == []


TAIL_SQL = {
    # HAVING alone: traced behind the final aggregate, in the program
    "having": ("select oid, sum(v) from items group by oid having sum(v) > 300", True, True),
    # a projection alone, and with HAVING before it
    "projection": ("select oid, sum(v) * 2, count(*) + 1 from items group by oid", True, True),
    "having_projection": ("select oid + 1 from items group by oid having count(*) > 12 and sum(v) < 9000",
                          True, True),
    # a string key passes through the tail as the exchange's packed words
    "string_key_having": ("select seg, count(*), sum(c_id) from cust group by seg having count(*) > 1", True, True),
    # a host-only function: the exchange serves the groups, the root the tail
    "having_host_only": ("select seg, count(*) from cust group by seg having replace(seg, 'A', 'Z') = 'Z'",
                         True, False),
    # ORDER BY / LIMIT tails are another tier decision (ROADMAP M9): refused
    "order_by": ("select oid, sum(v) from items group by oid having sum(v) > 300 order by oid", False, False),
    "order_by_limit": ("select oid, sum(v) from items group by oid order by sum(v) desc, oid limit 5", False, False),
    "limit": ("select oid, count(*) from items group by oid limit 5", False, False),
}


@pytest.mark.parametrize("case", list(TAIL_SQL))
def test_the_statement_tier_takes_a_having_or_projection_tail_and_no_ordering_one(case):
    """ISSUE 38's tail rule: TableScan [Selection]* Aggregation(GROUP BY)
    followed by Selections and Projections rides the exchange tier, the
    tail in the exchange program where the device can trace it and at the
    root where it cannot; an ORDER BY / LIMIT tail keeps to the tiers of
    `execute_root`. Every answer is the one that `tidb_allow_mpp` OFF
    gives."""
    from tidb_tpu.mpp.fragment import mesh_eligible, split_tail

    sql, taken, in_program = TAIL_SQL[case]
    s = _q3_session()
    m0, t0, f0 = M.MPP_SELECTS.value, M.MPP_TAIL_STATEMENTS.value, M.MPP_FALLBACKS.value
    rows = s.execute(sql).rows
    assert (M.MPP_SELECTS.value - m0, M.MPP_FALLBACKS.value - f0) == (int(taken), 0)
    assert M.MPP_TAIL_STATEMENTS.value - t0 == int(in_program)
    s.execute("set tidb_allow_mpp = OFF")
    off = s.execute(sql).rows
    if case == "limit":   # any five groups: each has to be one of the whole answer's
        whole = _canon(s.execute("select oid, count(*) from items group by oid").rows)
        assert len(rows) == len(off) == 5 and set(_canon(rows)) <= set(whole)
    elif "order_by" in case:
        assert [tuple(map(str, r)) for r in rows] == [tuple(map(str, r)) for r in off]
    else:
        assert _canon(rows) == _canon(off) and rows
    from tidb_tpu.parser import parse
    from tidb_tpu.sql.planner import plan_select

    stmt = parse(sql)
    dag = plan_select(stmt[0] if isinstance(stmt, list) else stmt, s.catalog).dag
    assert (mesh_eligible(dag) is not None) == taken and (split_tail(dag)[1] is not None) == taken


class TestNonUniqueRadixBuild:
    """The satellite pin: the radix kernel's expansion lift must agree
    with the monolithic join on duplicate build keys, escapes included."""

    @pytest.mark.parametrize("join_type", ["inner", "left_outer"])
    @pytest.mark.parametrize("strategy", ["search", "dense"])
    def test_duplicate_build_keys_match_monolithic(self, join_type, strategy):
        from tidb_tpu.expr.compile import CompVal
        from tidb_tpu.ops.join import hash_join
        from tidb_tpu.ops.radix_join import radix_hash_join

        rng = np.random.default_rng(11)
        nb, np_ = 512, 1024
        bk = rng.integers(0, 60, nb)          # heavy duplication
        pk = rng.integers(0, 80, np_)
        bvalid = rng.random(nb) < 0.9
        pvalid = rng.random(np_) < 0.9
        bnull = rng.random(nb) < 0.05
        pnull = rng.random(np_) < 0.05
        bcv = [CompVal(jnp.asarray(bk), jnp.asarray(bnull), I)]
        pcv = [CompVal(jnp.asarray(pk), jnp.asarray(pnull), I)]
        cap = 16384
        plan = (4, 256, 512, 2048)  # (n_parts, part_cap, probe_cap, esc_cap)
        res, _esc = radix_hash_join(
            bcv, pcv, jnp.asarray(bvalid), jnp.asarray(pvalid),
            join_type, cap, plan, strategy=strategy,
            build_unique=False, out_capacity=cap)
        ref = hash_join(bcv, pcv, jnp.asarray(bvalid), jnp.asarray(pvalid),
                        out_capacity=cap, join_type=join_type,
                        build_unique=False)
        assert not bool(res.overflow) and not bool(ref.overflow)

        def pairs(r):
            ov = np.asarray(r.out_valid)
            pi = np.asarray(r.probe_idx)[ov]
            bi = np.asarray(r.build_idx)[ov]
            nl = np.asarray(r.build_null)[ov]
            return sorted(
                (int(p), -1 if n else int(b)) for p, b, n in zip(pi, bi, nl))

        assert pairs(res) == pairs(ref)

    def test_non_unique_build_join_on_session_path(self):
        """End-to-end: a join keyed on a NON-unique build column rides the
        mpp tier and matches the root path."""
        s = _q3_session()
        sql = ("select ckey, count(*), sum(v) from items "
               "join ords on oid = ckey group by ckey")
        m0 = M.MPP_SELECTS.value
        rows = s.execute(sql).rows
        assert M.MPP_SELECTS.value == m0 + 1
        s.execute("set tidb_enable_tpu_mesh = OFF")
        assert _canon(rows) == _canon(s.execute(sql).rows)
