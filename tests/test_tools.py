"""Ecosystem tools: dump (Dumpling analog), LOAD DATA (Lightning analog
with resumable checkpoints), BACKUP/RESTORE (BR analog with checksums)
(ref: dumpling/export, pkg/lightning, br/pkg)."""

import json
import os

import pytest

from tidb_tpu.sql.catalog import Catalog
from tidb_tpu.sql.session import Session, SQLError
from tidb_tpu.store import TPUStore


@pytest.fixture()
def sess():
    s = Session()
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT, name VARCHAR(16))")
    s.execute("CREATE UNIQUE INDEX uv ON t (v)")
    s.execute("INSERT INTO t VALUES (1,10,'a'),(2,20,'b,c'),(3,NULL,NULL)")
    return s


# ---------------------------------------------------------------- dump


def test_dump_csv(sess, tmp_path):
    from tidb_tpu.tools import dump_table

    out = dump_table(sess, "t", str(tmp_path), fmt="csv")
    assert out["rows"] == 3
    lines = open(out["data_path"]).read().splitlines()
    assert lines[0] == "id,v,name"
    assert lines[2] == '2,20,"b,c"'  # quoting
    assert lines[3] == "3,\\N,\\N"  # nulls
    schema = open(out["schema_path"]).read()
    assert "PRIMARY KEY" in schema and "UNIQUE KEY `uv`" in schema


def test_dump_sql_reimportable(sess, tmp_path):
    from tidb_tpu.tools import dump_table

    out = dump_table(sess, "t", str(tmp_path), fmt="sql")
    s2 = Session()
    s2.execute(open(out["schema_path"]).read().rstrip().rstrip(";"))
    for stmt in open(out["data_path"]).read().split(";\n"):
        if stmt.strip():
            s2.execute(stmt)
    assert s2.execute("SELECT count(*) FROM t").values() == [[3]]
    assert s2.execute("SELECT name FROM t WHERE id = 2").values() == [["b,c"]]


def test_dump_all_consistent_snapshot(sess, tmp_path):
    from tidb_tpu.tools import dump_all

    sess.execute("CREATE TABLE u (id INT PRIMARY KEY)")
    sess.execute("INSERT INTO u VALUES (1)")
    out = dump_all(sess, str(tmp_path))
    assert set(out) == {"t", "u"}


# ---------------------------------------------------------------- load data


def test_load_data_basic(sess, tmp_path):
    p = tmp_path / "rows.tsv"
    p.write_text("4\t40\td\n5\t50\te\n6\t\\N\t\\N\n")
    r = sess.execute(f"LOAD DATA INFILE '{p}' INTO TABLE t")
    assert r.affected == 3
    assert sess.execute("SELECT count(*) FROM t").values() == [[6]]
    assert sess.execute("SELECT v, name FROM t WHERE id = 6").values() == [[None, None]]
    assert not os.path.exists(str(p) + ".ckpt")


def test_load_data_checkpoint_resume(sess, tmp_path):
    p = tmp_path / "rows.tsv"
    p.write_text("\n".join(f"{i}\t{i * 10}\tr{i}" for i in range(10, 20)) + "\n")
    # simulate a prior partial run: checkpoint says 4 rows are durable
    (tmp_path / "rows.tsv.ckpt").write_text("4")
    # make those 4 rows actually exist (as the crashed run would have left)
    sess.execute("INSERT INTO t VALUES (10,100,'r10'),(11,110,'r11'),(12,120,'r12'),(13,130,'r13')")
    r = sess.execute(f"LOAD DATA INFILE '{p}' INTO TABLE t")
    assert r.affected == 6  # only the tail imports
    assert sess.execute("SELECT count(*) FROM t WHERE id >= 10").values() == [[10]]


def test_load_data_duplicate_pk_fails(sess, tmp_path):
    p = tmp_path / "dup.tsv"
    p.write_text("1\t999\tx\n")
    with pytest.raises(SQLError, match="duplicate"):
        sess.execute(f"LOAD DATA INFILE '{p}' INTO TABLE t")


def test_load_data_indexes_maintained(sess, tmp_path):
    p = tmp_path / "rows.tsv"
    p.write_text("7\t70\tg\n")
    sess.execute(f"LOAD DATA INFILE '{p}' INTO TABLE t")
    # unique index uv must now see 70
    with pytest.raises(SQLError, match="duplicate"):
        sess.execute("INSERT INTO t VALUES (99, 70, 'clash')")


# ---------------------------------------------------------------- backup/restore


def test_backup_restore_roundtrip(sess, tmp_path):
    bdir = str(tmp_path / "bk")
    r = sess.execute(f"BACKUP DATABASE * TO '{bdir}'")
    assert r.columns == ["Destination", "Keys", "SnapshotTS"]
    store2, cat2 = TPUStore(), Catalog()
    s2 = Session(store2, cat2)
    r2 = s2.execute(f"RESTORE DATABASE * FROM '{bdir}'")
    assert r2.values()[0][2] == 1  # one table
    assert s2.execute("SELECT id, v, name FROM t ORDER BY id").values() == \
        sess.execute("SELECT id, v, name FROM t ORDER BY id").values()
    # index + autoid survive
    assert s2.execute("SELECT id FROM t WHERE v = 20").values() == [[2]]
    s2.execute("INSERT INTO t (v, name) VALUES (77, 'new')")
    assert s2.execute("SELECT max(id) FROM t").values() == [[4]]


def test_restore_rejects_existing_table(sess, tmp_path):
    bdir = str(tmp_path / "bk")
    sess.execute(f"BACKUP DATABASE * TO '{bdir}'")
    with pytest.raises(Exception, match="already exists"):
        sess.execute(f"RESTORE DATABASE * FROM '{bdir}'")


def test_restore_detects_corruption(sess, tmp_path):
    bdir = tmp_path / "bk"
    sess.execute(f"BACKUP DATABASE * TO '{bdir}'")
    seg = json.load(open(bdir / "manifest.json"))["segments"][0]["file"]
    data = bytearray((bdir / seg).read_bytes())
    data[-1] ^= 0xFF
    (bdir / seg).write_bytes(bytes(data))
    s2 = Session(TPUStore(), Catalog())
    with pytest.raises(Exception, match="checksum"):
        s2.execute(f"RESTORE DATABASE * FROM '{bdir}'")


def test_backup_resume_skips_valid_segments(sess, tmp_path):
    from tidb_tpu.tools import backup

    bdir = str(tmp_path / "bk")
    m1 = backup(sess.store, sess.catalog, bdir)
    m2 = backup(sess.store, sess.catalog, bdir)  # second run: resume path
    assert [s["sha256"] for s in m1["segments"]] == [s["sha256"] for s in m2["segments"]]


def test_brie_requires_super(sess, tmp_path):
    sess.execute("CREATE USER 'u'")
    store, cat = sess.store, sess.catalog
    u = Session(store, cat)
    u.user = "u"
    with pytest.raises(SQLError, match="SUPER"):
        u.execute(f"BACKUP DATABASE * TO '{tmp_path}/x'")


def test_backup_restore_views(sess, tmp_path):
    sess.execute("CREATE VIEW v_hi AS SELECT id, v FROM t WHERE v >= 20")
    bdir = str(tmp_path / "bk")
    sess.execute(f"BACKUP DATABASE * TO '{bdir}'")
    s2 = Session(TPUStore(), Catalog())
    s2.execute(f"RESTORE DATABASE * FROM '{bdir}'")
    assert s2.execute("SELECT id FROM v_hi ORDER BY id").values() == [[2]]


# ------------------------------------------------- tidb-vet (ISSUE 7 + 9)

def test_vet_repo_is_clean():
    """Tier-1 gate: every tidb-vet pass — the lexical families, the
    interprocedural dataflow passes, the jaxpr auditor and the
    stale-suppression audit — reports zero findings on the live tree
    (the fixture corpus in tests/vet_fixtures/ proves each pass CAN
    fire; see tests/test_vet.py)."""
    from tidb_tpu import analysis

    findings = analysis.run_all()
    assert findings == [], "\n".join(f.render() for f in findings)
    # the suite really covers all the families (error-taxonomy was
    # promoted into dataflow-error-escape in ISSUE 9)
    assert set(analysis.PASSES) == {
        "jit-purity", "lock-discipline", "metrics", "wire-parity",
        "failpoints", "dataflow-snapshot", "dataflow-backoff",
        "dataflow-error-escape", "jax-audit",
    }
    assert analysis.SUPPRESSIONS == "suppressions"


def test_vet_baseline_json_roundtrips():
    """ISSUE 9 satellite: --baseline emits stable sorted JSON that
    --diff reads back byte-for-byte (the cross-commit diffing seam) —
    asserted here at the library level; tests/test_vet.py drives the
    CLI end to end."""
    import json

    from tidb_tpu import analysis

    findings = analysis.run_all()
    dicts = [f.to_dict() for f in findings]
    assert dicts == sorted(dicts, key=lambda d: (d["path"], d["line"], d["pass"]))
    assert json.loads(json.dumps(dicts)) == dicts


def test_load_data_lock_conflict_is_a_sql_error(sess, tmp_path):
    """Pin for the live finding dataflow-error-escape surfaced (ISSUE 9):
    LOAD DATA hitting a key held by a live transaction must surface a
    typed SQLError, not a raw KeyIsLocked engine exception escaping the
    session boundary."""
    from tidb_tpu.codec import tablecodec
    from tidb_tpu.store.txn import KeyIsLocked

    p = tmp_path / "rows.tsv"
    p.write_text("9\t90\tz\n")
    meta = sess.catalog.table("t")
    key = tablecodec.encode_row_key(meta.table_id, 9)
    lock_ts = sess.store.next_ts()
    sess.store.txn.prewrite({key: b"\x00"}, key, lock_ts)
    try:
        with pytest.raises(SQLError, match="locked"):
            sess.execute(f"LOAD DATA INFILE '{p}' INTO TABLE t")
    except KeyIsLocked as exc:  # the pre-fix failure mode, kept loud
        pytest.fail(f"KeyIsLocked escaped the session boundary: {exc}")
    finally:
        sess.store.txn.release_all(lock_ts)
    # with the lock gone the import succeeds
    assert sess.execute(f"LOAD DATA INFILE '{p}' INTO TABLE t").affected == 1


# ------------------------------------------------------- failpoint_check

def test_failpoint_check_repo_is_clean():
    """Tier-1 gate (ISSUE 6 satellite): every failpoint name armed in
    tests/ and tools/ resolves to a real eval/is_armed/peek site in
    tidb_tpu/, and every site carries a catalog description — a typo'd
    name silently never fires, so this is the only guard."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import failpoint_check

    errors, sites = failpoint_check.check()
    assert errors == []
    # the fault-injection surface this PR added is part of the catalog
    for name in ("store/unreachable", "store/not-leader", "store/server-busy",
                 "pd/heartbeat-lost", "pd/operator-timeout"):
        assert name in sites, name


def test_failpoint_check_catches_a_typo(tmp_path):
    """A use of an undefined name must be reported (the failure mode the
    tool exists for)."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import failpoint_check

    # the bogus name is spliced in at runtime so the checker's own scan of
    # THIS file (it caught the literal form — proof it works) stays clean
    typo = "store/" + "unreachble"
    bogus = 'from tidb_tpu.util import failpoint\nfailpoint.enable(%r)\n' % typo
    uses = failpoint_check._scan(failpoint_check._USE, [str(tmp_path / "t.py")])
    assert uses == {}  # unreadable/missing file: no crash
    p = tmp_path / "t.py"
    p.write_text(bogus)
    uses = failpoint_check._scan(failpoint_check._USE, [str(p)])
    assert typo in uses


def test_failpoint_catalog_generation(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import failpoint_check

    _errors, sites = failpoint_check.check()
    out = tmp_path / "FAILPOINTS.md"
    failpoint_check.write_catalog(sites, str(out))
    text = out.read_text()
    assert "| `store/server-busy` |" in text
    assert "| `pd/operator-timeout` |" in text
    for name in sites:
        assert f"| `{name}` |" in text


# ------------------------------------------------------- chip smoke (CPU)


def test_chip_smoke_cpu_rehearsal(tmp_path, monkeypatch, capsys):
    """chip_smoke.py's phases 2-4 at 4,096 rows on the CPU: a PR that
    breaks an entry point the smoke uses is caught without chip time.
    The device assertion (phase 1) is not called and the Pallas-kernel
    assertion is switched off here — on the CPU no kernel is traced."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import chip_smoke as cs

    from tidb_tpu.util import failpoint

    monkeypatch.setattr(cs, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(cs, "require_pallas", lambda stmt, traced: None)
    ctx = cs.Ctx(rows=4096, seed=0)
    try:
        with failpoint.enabled("cop-debug-raise"):
            cs.phase_load(ctx)
            cs.phase_row_store(ctx)
            cs.phase_columnar(ctx)
    finally:
        ctx.close()
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["phase"] for x in lines if "phase" in x] == ["load", "row_store", "columnar"]
    assert {x["stmt"] for x in lines if "stmt" in x} >= {"q6", "q1", "q3", "columnar_q1"}
    # Q3 again with another SEGMENT and another DATE: the string rode as an operand, nothing was built
    draws = [x for x in lines if x.get("stmt") == "q3_params"]
    assert [(x["segment"], x["date"]) for x in draws] == [(p["segment"], p["date"]) for p in cs.Q3_DRAWS]
    assert all(x["program_compiles"] == x["xla_compiles"] == 0 and x["program_str_params_bound"] == 1 for x in draws)
    assert not os.listdir(tmp_path)  # the data files are gone again


def test_chip_smoke_mesh_phase_cpu_rehearsal(tmp_path, monkeypatch, capsys):
    """chip_smoke.py's `--chips 4` phase at 4,096 rows on the suite's eight
    host devices: `SPLIT TABLE` over the wire lays the regions out, Q6, Q1
    and Q3 are one cross-chip launch each with collectives in the program's
    text and no fall-back, each shape's second draw and three more
    (SEGMENT, DATE) draws build nothing and find their lanes resident, and
    the single-device run answers the same."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import chip_smoke as cs

    from tidb_tpu.exec import builder
    from tidb_tpu.util import failpoint

    monkeypatch.setattr(cs, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(cs, "MESH_PROGRAMS", [])
    monkeypatch.setattr(builder, "build_program", builder.build_program)   # the watcher's wrapper goes with the test
    ctx = cs.Ctx(rows=4096, seed=1)
    try:
        with failpoint.enabled("cop-debug-raise"):
            cs.phase_load(ctx)
            cs.watch_mesh_programs()
            cs.phase_mesh(ctx, 8)
    finally:
        ctx.close()
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    (split,) = [x for x in lines if x.get("phase") == "split"]
    assert split["regions"] == {"lineitem": 16, "orders": 8}
    stmts = {x["stmt"]: x for x in lines if "stmt" in x}
    for name in ("q6", "q1", "q3"):
        on, off = stmts[f"mesh_{name}"], stmts[f"single_device_{name}"]
        assert on["tiers"]["MESH_COP_BATCHES"] + on["tiers"]["MPP_SELECTS"] == 1 and not any(off["tiers"].values())
        assert on["second_draw_stack"] == {"hits": 1, "misses": 0} and "second_draw_stack" not in off
        assert on["rows"] == off["rows"]
        assert all(p["devices"] == 8 and p["collectives"] for p in on["mesh_programs"]) and on["mesh_programs"]
    draws = [x for x in lines if x.get("stmt") == "mesh_q3_params"]
    assert [(x["segment"], x["date"]) for x in draws] == [(p["segment"], p["date"]) for p in cs.Q3_DRAWS]
    assert all(x["program_compiles"] == x["xla_compiles"] == 0 and x["mesh_cop_batches"] == 1 for x in draws)
    assert all((x["mesh_stack_hits"], x["mesh_stack_misses"]) == (1, 0) for x in draws)
