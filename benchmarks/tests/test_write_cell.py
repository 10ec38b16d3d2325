"""The cell kept ready in `data/write_cells.json`, `sysbench_rw_uniform`,
driven whole on the CPU: `correct` on sound runs, false under the control
(`lost_commit`) and under a program that serves stale snapshots.

The CPU holds no 16 clients over 524,288 rows, and at 16 clients over a
few thousand rows two transactions write one row at once and today's
program fails the later one (errno 1105, which the mix does not restart).
So the runs here take 2 clients over 8 x 12,000 rows: a transaction writes
3 rows and overlaps with at most one other of 3 rows, so the chance that
one of a run's M transactions conflicts is about M x 9 / 96,000, 0.25 %
for the ~26 that a run of SECONDS makes on the CPU (checked on every sound
run, with room for a host four times as fast)."""

import json
import os

import pytest

from conftest import HERE, small_run_at

CELL = "sysbench_rw_uniform"
CONFIG = "sysbench_32x16k_rw"
SIZES = {CONFIG: {"tables": 8, "table_size": 12000, "insert_batch_rows": 3000}}
CLIENTS = 2
SECONDS = 1.0
ROWS = SIZES[CONFIG]["tables"] * SIZES[CONFIG]["table_size"]


@pytest.fixture
def rw_run(tmp_path, monkeypatch, capsys):
    return small_run_at(tmp_path, monkeypatch, capsys, SIZES, CLIENTS)


def _conflict_chance(line: dict) -> float:
    """The reckoned chance that some transaction of the run met another
    writing one of its rows: 3 rows against the other client's 3."""
    transactions = line["compared"]["histories_over_cap"]["of"]
    return transactions * 9 * (CLIENTS - 1) / ROWS


def test_entries_are_the_kept_ready_ones():
    with open(os.path.join(HERE, "data", "write_cells.json")) as f:
        kept = json.load(f)
    (cell,) = kept["workloads"]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, CONFIG, "rw_uniform", 1)
    (config,) = kept["configs"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}/config.json" and config["reduced"] == ["table_size"]
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert CELL not in {w["name"] for w in manifest["workloads"]}       # kept ready, not in the benchmark


@pytest.mark.parametrize("seed", [2**31 + 101, 7, 3_000_000_019])
def test_sound_run_is_correct(rw_run, seed):
    line = rw_run(CELL, seed, SECONDS)
    compared = line["compared"]
    assert line["correct"] is True, compared
    assert list(compared) == ["wrong_answers", "traced_wrong_row_counts", "failed_operations",
                              "unanswered_clients", "statements_compared", "read_back_mismatches",
                              "rows_read_back", "histories_over_cap", "restarted_attempts",
                              "reads_with_concurrent_writers"]
    assert compared["rows_read_back"]["value"] >= compared["rows_read_back"]["at_least"] == 1
    assert compared["read_back_mismatches"] == {"value": 0, "limit": 0, "of": compared["read_back_mismatches"]["of"]}
    assert compared["statements_compared"]["value"] > 0 and line["failed"] == 0
    assert _conflict_chance(line) < 0.01, line["attempted"]


@pytest.mark.parametrize("seed", [2**31 + 203, 11, 3_000_000_029])
def test_control_is_not_correct(rw_run, seed):
    line = rw_run(CELL, seed, SECONDS, control=True)
    assert line["control"].startswith("lost_commit")
    assert line["correct"] is False
    assert line["compared"]["read_back_mismatches"]["value"] > 0


def test_stale_snapshot_is_not_correct(rw_run, monkeypatch):
    """The fault: every read is served at the snapshot of the load's end,
    whatever has committed since."""
    from tidb_tpu.sql import session as session_mod

    real = session_mod.Session._read_ts
    frozen = {}

    def stale(self, *a, **k):
        ts = real(self, *a, **k)
        if self.txn is not None and self.txn.explicit:
            return frozen.setdefault("ts", ts)
        return frozen.get("ts", ts)

    monkeypatch.setattr(session_mod.Session, "_read_ts", stale)
    line = rw_run(CELL, 2**31 + 307, SECONDS)
    assert frozen
    assert line["correct"] is False
    assert line["compared"]["read_back_mismatches"]["value"] > 0 or line["compared"]["wrong_answers"]["value"] > 0


def test_traced_run(rw_run):
    """Every second operation is sent as TRACE, its writes too: their span
    trees carry the affected rows, which are compared."""
    line = rw_run(CELL, 2**31 + 409, SECONDS, trace=True)
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["traced_wrong_row_counts"]["of"] > 0 and line["failed"] == 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert {"frontend_ms_per_op", "launches_per_op", "server_ms_per_op"} <= set(line["metrics"])
    assert _conflict_chance(line) < 0.01


def test_altered_answer_is_not_correct(rw_run, monkeypatch):
    """The fault of `test_correct.py`: every seventh result set the session
    produces has its first row's last character changed."""
    from tidb_tpu.sql import session as session_mod
    from tidb_tpu.types import Datum

    real = session_mod.Session.execute
    seen = {"n": 0}

    def altered(self, sql, *a, **k):
        result = real(self, sql, *a, **k)
        if sql.lstrip().lower().startswith("select") and result.rows:
            seen["n"] += 1
            if seen["n"] % 7 == 0:
                cell = result.rows[0][-1]
                text = str(cell.to_python() if hasattr(cell, "to_python") else cell)
                result.rows[0] = list(result.rows[0][:-1]) + [Datum.string(text[:-1] + ("1" if text[-1] != "1" else "2"))]
        return result

    monkeypatch.setattr(session_mod.Session, "execute", altered)
    line = rw_run(CELL, 2**31 + 503, SECONDS)
    assert seen["n"] >= 7 and line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0


def test_acknowledged_but_lost_commit_is_not_correct(rw_run, monkeypatch):
    """The fault on the write path: every fourth COMMIT of a transaction
    that wrote is answered OK and applies nothing."""
    from tidb_tpu.sql import session as session_mod

    real = session_mod.Session._commit
    seen = {"n": 0}

    def lossy(self):
        if self.txn is not None and self.txn.explicit and self.txn.mutations:
            seen["n"] += 1
            if seen["n"] % 4 == 0:
                self.txn.mutations.clear()
        return real(self)

    monkeypatch.setattr(session_mod.Session, "_commit", lossy)
    line = rw_run(CELL, 2**31 + 607, SECONDS)
    assert seen["n"] >= 4 and line["correct"] is False
    assert line["compared"]["read_back_mismatches"]["value"] > 0
