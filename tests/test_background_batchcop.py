"""Batch coprocessor + PD scatter + exchange modes + background frameworks
(ref: copr/batch_coprocessor.go, PD scatter, mpp_exec.go:669-719 partition
modes, pkg/timer, pkg/ttl, pkg/disttask, statistics auto-analyze)."""

import numpy as np
import pytest

from tidb_tpu.sql.session import Session, SQLError


# ---------------------------------------------------------------- batch cop


def test_batch_cop_matches_plain():
    s = Session()
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT)")
    s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i % 13})" for i in range(1, 501)))
    # split into several regions, scattered over 4 stores
    from tidb_tpu.codec import tablecodec

    for h in (100, 200, 300, 400):
        s.store.cluster.split(tablecodec.encode_row_key(s.catalog.table("t").table_id, h))
    s.store.cluster.set_stores(4)
    plain = s.execute("SELECT count(*), sum(v) FROM t WHERE v < 7").values()
    s.execute("SET tidb_allow_batch_cop = ON")
    batched = s.execute("SELECT count(*), sum(v) FROM t WHERE v < 7").values()
    assert plain == batched


def test_scatter_assignment():
    from tidb_tpu.store.region import Cluster

    c = Cluster()
    for k in (b"b", b"d", b"f", b"h"):
        c.split(k)
    c.set_stores(3)
    stores = {c.store_of(r.region_id) for r in c.regions()}
    assert stores == {0, 1, 2}  # every store got regions


# ---------------------------------------------------------------- exchanges


def _mesh8():
    import jax

    devs = np.array(jax.devices()[:8])
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    from jax.sharding import Mesh

    return Mesh(devs, ("x",))


def test_broadcast_exchange():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tidb_tpu.mpp.exchange_op import broadcast_exchange

    mesh = _mesh8()
    n = 4
    vals = jnp.arange(8 * n, dtype=jnp.int64)
    valid = jnp.ones(8 * n, bool)

    def body(v, m):
        (out,), gv = broadcast_exchange("x", [v], m)
        # every device must hold every row
        return jnp.sum(jnp.where(gv, out, 0))[None]

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"))
    got = f(vals, valid)
    assert np.all(np.asarray(got) == int(vals.sum()))


def test_passthrough_exchange():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tidb_tpu.mpp.exchange_op import passthrough_exchange

    mesh = _mesh8()
    n = 4
    vals = jnp.arange(8 * n, dtype=jnp.int64)
    valid = jnp.ones(8 * n, bool)

    def body(v, m):
        (out,), gv = passthrough_exchange("x", [v], m, target=0)
        return jnp.sum(jnp.where(gv, out, 0))[None]

    got = np.asarray(jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"))(vals, valid))
    # only device 0 owns rows; everyone else sums to zero
    assert got[0] == int(vals.sum()) and np.all(got[1:] == 0)


# ---------------------------------------------------------------- background


def test_timer_fires_and_survives_errors():
    from tidb_tpu.background import Timer

    calls = []

    def fn():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("boom")

    t = Timer("t", 0.01, fn).start()
    import time

    time.sleep(0.15)
    t.stop()
    assert len(calls) >= 3
    assert t.error_count >= 1 and t.fire_count >= 1


def test_ttl_worker_deletes_expired():
    from tidb_tpu.background import TTLWorker

    s = Session()
    s.execute("CREATE TABLE ev (id INT PRIMARY KEY, created DATETIME)")
    s.execute("INSERT INTO ev VALUES (1,'2024-01-01 00:00:00'),(2,'2024-06-01 00:00:00'),(3,'2024-12-01 00:00:00')")
    w = TTLWorker(s, now_fn=lambda: "2024-12-02 00:00:00")
    w.attach("ev", "created", expire_after_days=30.0)
    deleted = w.run_once()
    assert deleted == 2
    assert s.execute("SELECT id FROM ev").values() == [[3]]
    assert w.run_once() == 0  # idempotent


def test_ttl_rejects_unknown_column():
    from tidb_tpu.background import TTLWorker

    s = Session()
    s.execute("CREATE TABLE ev (id INT PRIMARY KEY)")
    with pytest.raises(Exception):
        TTLWorker(s).attach("ev", "nope", 1.0)


def test_disttask_scheduler():
    from tidb_tpu.background import DistTaskScheduler

    sched = DistTaskScheduler(n_workers=4)
    task = sched.run("square", list(range(20)), lambda p: p * p)
    assert task.state == "succeed"
    assert sorted(st.result for st in task.subtasks) == sorted(i * i for i in range(20))


def test_disttask_retry_then_revert():
    from tidb_tpu.background import DistTaskScheduler

    sched = DistTaskScheduler(n_workers=2, max_retries=1)

    def flaky(p):
        if p == 13:
            raise RuntimeError("always fails")
        return p

    task = sched.run("flaky", [1, 13, 2], flaky)
    assert task.state == "reverted"
    failed = [st for st in task.subtasks if st.state == "failed"]
    assert failed and failed[0].payload == 13 and failed[0].attempts == 2


def test_auto_analyze_triggers_on_drift():
    from tidb_tpu.background import AutoAnalyzer

    s = Session()
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT)")
    s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i})" for i in range(1, 11)))
    a = AutoAnalyzer(s)
    assert a.run_once() == ["t"]  # no stats yet
    assert a.run_once() == []  # fresh stats, no drift
    s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i})" for i in range(11, 31)))
    assert a.run_once() == ["t"]  # 200% growth > 50% ratio
