"""`correct` has to come out false when it should.  Each test drives a
whole run of the harness on the CPU at a small size (conftest's
`small_run`): as it stands, with the configuration's control in the
program's place, and with the timed path broken underneath so that an
answer is altered where it is produced."""

import pytest

CELLS = ["sysbench_ro_uniform", "tpch_q1q6_params", "tpch_q3_params"]
SECONDS = {"sysbench_ro_uniform": 12.0, "tpch_q1q6_params": 6.0, "tpch_q3_params": 6.0}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_control_is_not(small_run, capsys, workload):
    line = small_run(workload, 2**31 + 17, SECONDS[workload], control=True)
    # one process reads both: the program's verdict on an earlier line,
    # the control's as the result, with the numbers compared last
    assert list(line)[-1] == "compared"
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0
    assert line["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run(small_run, workload):
    from harness import catalog

    line = small_run(workload, 23, SECONDS[workload])
    assert line["correct"] is True
    assert line["compared"]["wrong_answers"] == {
        "value": 0, "limit": 0, "of": line["compared"]["statements_compared"]["value"]}
    assert set(line["metrics"]) == {m["name"] for m in catalog.Cell(workload).metrics("end_to_end")}
    assert {"ops_per_s", "op_p50_ms", "setup_s"} <= set(line["metrics"])
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run(small_run, workload):
    from harness import catalog

    line = small_run(workload, 31, SECONDS[workload], trace=True)
    assert line["correct"] is True
    assert line["compared"]["traced_wrong_row_counts"]["of"] > 0
    wanted = {m["name"] for m in catalog.Cell(workload).metrics("per_layer")} - {"device_roofline"}
    assert wanted <= set(line["metrics"])     # no peaks on the CPU, so no roofline
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(small_run, monkeypatch, workload):
    """The fault: every seventh result set that the session produces has
    the last character of its first row's last column changed."""
    from tidb_tpu.sql import session as session_mod
    from tidb_tpu.types import Datum

    real = session_mod.Session.execute
    seen = {"n": 0}

    def altered(self, sql, *a, **k):
        result = real(self, sql, *a, **k)
        if sql.lstrip().lower().startswith("select") and result.rows:
            seen["n"] += 1
            if seen["n"] % 7 == 0:
                text = str(result.rows[0][-1].to_python() if hasattr(result.rows[0][-1], "to_python")
                           else result.rows[0][-1])
                flipped = text[:-1] + ("1" if text[-1] != "1" else "2")
                result.rows[0] = list(result.rows[0][:-1]) + [Datum.string(flipped)]
        return result

    monkeypatch.setattr(session_mod.Session, "execute", altered)
    line = small_run(workload, 29, SECONDS[workload])
    assert seen["n"] >= 7
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0
