"""The cell `tpch_q18_mesh4` (configuration `tpch_sf0p02_q18_mesh4`, mix
`q18_params`) and its per-layer metrics: the manifest's entries, the mix
through `test_traffic.py`'s rules, the deployment's Q18 reference, control
and bytes, whole small runs of the cell on the CPU's host devices (plain,
traced, under `--control`), and the new readers on a window with the
counters named and without.  The cross-chip tiers need more than one
device, so this file asks the CPU backend for four before JAX starts one,
as `test_mesh_cell.py` does."""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import test_traffic  # noqa: E402
from harness import catalog, judge  # noqa: E402
from harness.traffic import Step, client_rng  # noqa: E402

CELL = "tpch_q18_mesh4"
COP = "distsql + store cop / columnar route"
NEW = ("mpp_exchange_ms_per_op", "mpp_selects_per_op", "mpp_fallbacks_per_op", "mpp_exchanged_bytes_per_op",
       "mpp_tail_ms_per_op", "subquery_ms_per_op", "mpp_roofline")
# test_traffic.py finds every mix by its file and its configuration in this
# table, which a PR that adds a mix cannot edit: the new mix's row is put
# there as the tests are collected, so its rules run over it too
test_traffic.CONFIG_OF.setdefault("q18_params", "tpch_sf0p02_q18_mesh4")


@pytest.fixture(scope="module")
def cell():
    return catalog.Cell(CELL)


@pytest.fixture(scope="module")
def data(cell):
    return cell.deployment.generate(dict(cell.config, lineitem_rows=4096), 2**31 + 5)


def test_the_cell_is_in_the_manifest_with_its_configuration_and_metrics(cell):
    assert (cell.chips, cell.entry["config"], cell.entry["traffic"]) == (4, "tpch_sf0p02_q18_mesh4", "q18_params")
    mesh4 = catalog.Cell("tpch_q1q6q3_mesh4").config["layout"]
    assert {k: cell.config["layout"][k] for k in ("chips", "regions", "split")} == {
        k: mesh4[k] for k in ("chips", "regions", "split")}
    assert set(cell.statements) == {"q18"} and callable(cell.deployment.load)
    per_layer = {m["name"]: m for m in cell.metrics("per_layer")}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL] and callable(cell.reader("per_layer", name))
    assert {per_layer[n]["layer"] for n in ("mpp_selects_per_op", "mpp_fallbacks_per_op", "mpp_tail_ms_per_op")} == {COP}
    four = [w["name"] for w in cell.manifest["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(cell.manifest["workloads"]) // 2


def test_the_mix_draws_quantity_inside_the_specs_range():
    mix = test_traffic._mix("q18_params")
    assert mix.clients == 2 and mix.spec["read_engines"] == "tpu" and mix.statement_names() == ["q18"]
    rng = client_rng(2**31 + 13, 0, 1)
    seen = set()
    for _ in range(200):
        (q18,) = mix.operation(rng)
        assert "{" not in q18.sql and f"> {q18.params['quantity']})" in q18.sql
        seen.add(q18.params["quantity"])
    assert seen == {312, 313, 314, 315}


def test_the_reference_keeps_the_orders_past_quantity_and_the_control_doubles_them(cell, data):
    dep = cell.deployment
    sums = dep.order_quantities(data)
    assert int(sums.sum()) == int(data["lineitem"]["quantity"].sum())
    for q in (150, 250, 313):
        want = dep.reference("q18", {"quantity": q}, data)
        assert set(want) == set(dep.ref_q18_inner(data, {"quantity": q}))
        assert all(w[4] > q for w in want.values()) and len(want) == int((sums > q).sum())
        top = dep._top(want)
        rows = [[w[0], str(w[1]), str(k), w[2], f"{w[3]:.2f}", f"{w[4]:.2f}"] for k, w in top]
        assert dep.mismatch("q18", want, rows) is None and dep.expected_rows("q18", want) == len(rows) <= 100
        control = dep.control("q18", {"quantity": q}, data)
        assert len(control) == 100
        # past 100 orders both answers are the same 100 of highest o_totalprice;
        # at the spec's QUANTITY the reference keeps a handful, the control 100
        assert (dep.mismatch("q18", want, control) is None) == (len(want) >= 100 and q == 150)
    assert dep.mismatch("q18", {}, []) is None and dep.mismatch("q18", {}, [["x"]]) is not None
    assert dep.reference("q6", {"date": "1994-01-01", "discount": "0.06", "quantity": 24}, data) is not None


def test_scan_bytes_count_the_columns_both_statements_read(cell):
    c = cell.config
    assert cell.deployment.scan_bytes("q18", c) == (32 * c["lineitem_rows"] + 28 * c["orders_rows"]
                                                    + 33 * c["customer_rows"])


# ---- whole small runs (conftest's small_run): no measurements

def test_sound_run_is_correct_and_the_inner_statement_rides_the_exchange(small_run):
    line = small_run(CELL, 2**31 + 29, 8.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["statements_compared"]["value"] >= 2
    assert {"ops_per_s", "op_p50_ms", "setup_s"} == set(line["metrics"])
    assert line["device"]["count"] == 4


def test_traced_run_reports_the_cells_metrics(small_run, cell):
    line = small_run(CELL, 37, 8.0, trace=True)
    assert line["correct"] is True and line["compared"]["traced_wrong_row_counts"]["of"] > 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    wanted = {x["name"] for x in cell.metrics("per_layer")} - {"mpp_roofline"}   # no peaks for the CPU
    assert wanted <= set(m)
    assert m["mpp_selects_per_op"] == 1.0 and m["mpp_fallbacks_per_op"] == 0.0
    assert m["programs_built_per_op"] == 0.0 and m["eager_compiles_per_op"] == 0.0
    assert m["mpp_exchanged_bytes_per_op"] > 0 and m["mpp_tail_ms_per_op"] >= 0 and m["subquery_ms_per_op"] > 0


def test_control_is_not_correct(small_run):
    line = small_run(CELL, 2**31 + 19, 8.0, control=True)
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["wrong_answers"]["value"] > 0 and line["control"].startswith("doubled_merge")


# ---- the readers

def test_counter_readers_with_the_names_and_without():
    window = {"attempted": 4, "counters": {"mpp_selects": 4, "mpp_fallbacks": 0, "mpp_exchanged_bytes": 4096}}
    read = {n: catalog.Cell.reader("per_layer", n) for n in NEW}
    assert read["mpp_selects_per_op"](window) == 1.0 and read["mpp_fallbacks_per_op"](window) == 0.0
    assert read["mpp_exchanged_bytes_per_op"](window) == 1024.0
    for n in ("mpp_selects_per_op", "mpp_fallbacks_per_op", "mpp_exchanged_bytes_per_op"):
        assert read[n]({"attempted": 4, "counters": {}}) is None and read[n](dict(window, attempted=0)) is None


def test_span_readers_and_the_roofline_read_what_the_run_holds():
    read = {n: catalog.Cell.reader("per_layer", n) for n in NEW}
    run = {"self_times_ms_per_op": {"mpp.exchange": 2.5, "mpp.tail": 0.01, "session.subquery": 0.2}}
    assert (read["mpp_exchange_ms_per_op"](run), read["mpp_tail_ms_per_op"](run),
            read["subquery_ms_per_op"](run)) == (2.5, 0.01, 0.2)
    for n in ("mpp_exchange_ms_per_op", "mpp_tail_ms_per_op", "subquery_ms_per_op"):
        assert read[n]({"self_times_ms_per_op": {}}) is None and read[n]({}) is None   # the parent: no such span
    profile = {"busy_s": 0.5, "needed_bytes": 819_000_000, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert read["mpp_roofline"]({"profile": profile}) == pytest.approx(0.2)
    assert read["mpp_roofline"]({"profile": profile}) == catalog.Cell.reader("per_layer", "device_roofline")(
        {"profile": profile})
    assert read["mpp_roofline"]({"profile": dict(profile, peaks=None)}) is None
    assert np.isfinite(read["mpp_roofline"]({"profile": dict(profile, busy_s=1e-9)}))


# ---- faults of the exchange against the window and the set-up check
#
# The lanes as the cell lays them out: lineitem's rows, loaded in the
# generator's order (by orderkey), cut into 8 regions of equal handle
# ranges, two to a chip.  Three faults of the exchange aggregate, as sums by
# order: `doubled` (two chips' copies of a group merged, the control),
# `dropped_chip` (one chip's partial state lost) and `no_exchange` (the
# all_to_all left out: each chip finishes the partial groups of its own
# lanes, so an order whose lines straddle two chips is two groups).

LANES, CHIPS = 8, 4
FAULTS = ("doubled", "dropped_chip", "no_exchange")
SWEEP = [2038000000 + 7919 * i for i in range(48)]


def _partials(data):
    l = data["lineitem"]
    n_rows, n_orders = len(l["oidx"]), len(data["orders"]["orderkey"])
    chip = (np.arange(n_rows) * LANES // n_rows) // (LANES // CHIPS)
    part = np.zeros((CHIPS, n_orders), np.int64)
    np.add.at(part, (chip, l["oidx"]), l["quantity"])
    held = np.zeros((CHIPS, n_orders), bool)
    held[chip, l["oidx"]] = True
    return part, held


def _fault_inner(data, fault, q, parts=None) -> list:
    """The inner statement's orderkeys (a multiset) as the faulty exchange
    answers them at QUANTITY q (`parts`: `_partials(data)`)."""
    keys, (part, held) = data["orders"]["orderkey"], parts or _partials(data)
    if fault == "no_exchange":
        return sorted(int(k) for c in range(CHIPS) for k in keys[(part[c] > q) & held[c]])
    total = {"none": part.sum(0), "doubled": 2 * part.sum(0), "dropped_chip": part[1:].sum(0)}[fault]
    return sorted(int(k) for k in keys[total > q])


def _fault_q18(dep, data, fault, q, parts=None, every=None) -> list:
    """Q18's rows, as wire text, over the faulty inner answer: the outer
    statement's semi join keeps an order once however often it is in the
    set, and sums l_quantity itself (`every`: Q18's groups at QUANTITY 0)."""
    inner = set(_fault_inner(data, fault, q, parts))
    every = every or dep.ref_q18(data, {"quantity": 0})
    want = {k: w for k, w in every.items() if k in inner}
    return [[w[0], str(w[1]), str(k), w[2], f"{w[3]:.2f}", f"{w[4]:.2f}"] for k, w in dep._top(want)]


class _Faulty:
    """A connection that answers the set-up check as the faulty exchange."""

    def __init__(self, data, fault):
        self.data, self.fault = data, fault

    def query(self, sql):
        if "having" not in sql:
            return [], []
        q = int(sql.rsplit(">", 1)[1])
        return ["l_orderkey"], [[str(k)] for k in _fault_inner(self.data, self.fault, q)]


@pytest.fixture(scope="module")
def sweep(cell):
    """Per fault, on how many of SWEEP's seeds at the cell's size the
    window's comparison at QUANTITY 312-315 reads the faulty Q18 wrong
    (through the judge), the set-up check ends the run, and the fault
    changes the inner statement's answer at any threshold at all."""
    dep = cell.deployment
    out = {f: {"window": 0, "check": 0, "changes": 0} for f in FAULTS}
    out["non_empty_share"] = []
    for seed in SWEEP:
        data = dep.generate(cell.config, seed)
        parts, every = _partials(data), dep.ref_q18(data, {"quantity": 0})
        kept = [len(dep.ref_q18_inner(data, {"quantity": q})) for q in dep.SPEC_QUANTITIES]
        out["non_empty_share"].append(sum(n > 0 for n in kept) / len(kept))
        for fault in FAULTS:
            checker = judge.Checker(dep, data)
            for q in dep.SPEC_QUANTITIES:
                checker.statement(Step("", "q18", {"quantity": q}), _fault_q18(dep, data, fault, q, parts, every), "window")
            out[fault]["window"] += not checker.window([], 0)["correct"]
            try:
                dep.check_inner(_Faulty(data, fault), data, lambda **_line: None)
            except SystemExit:
                out[fault]["check"] += 1
            out[fault]["changes"] += any(_fault_inner(data, fault, q, parts) != _fault_inner(data, "none", q, parts)
                                         for q in (*range(0, 351, 10), *dep.SPEC_QUANTITIES))
    return out


def test_the_fault_models_hold_the_reference_where_nothing_is_lost(cell, data):
    dep = cell.deployment
    for q in (0, 150, 313):
        assert _fault_inner(data, "none", q) == sorted(dep.ref_q18_inner(data, {"quantity": q}))
        want = dep.reference("q18", {"quantity": q}, data)
        assert dep.mismatch("q18", want, _fault_q18(dep, data, "none", q)) is None
    assert dep.check_inner(_Faulty(data, "none"), data, lambda **_line: None) is None


def test_the_window_at_the_specs_quantity_sees_an_over_count_and_rarely_an_under_count(sweep):
    """At 312-315 most seeds keep no order at all: the window's comparison
    reads every over-count wrong and few under-counts."""
    n = len(SWEEP)
    assert sweep["doubled"]["window"] == n
    assert sweep["dropped_chip"]["window"] < n // 4 and sweep["no_exchange"]["window"] < n // 4
    assert np.mean(sweep["non_empty_share"]) < 0.5 and sweep["non_empty_share"].count(0.0) > n // 2


def test_the_set_up_check_ends_every_run_that_a_fault_changes(sweep):
    """The check at QUANTITY 0, 150 and 250 ends the run on every seed on
    which the fault changes the inner statement's answer at any threshold:
    all of them for a lost or doubled partial state; for a missing exchange,
    those on which an order straddles two chips (else each chip's groups
    are already whole and every answer is right)."""
    for fault in FAULTS:
        assert sweep[fault]["check"] == sweep[fault]["changes"], (fault, sweep[fault])
    assert sweep["doubled"]["check"] == sweep["dropped_chip"]["check"] == len(SWEEP)
    assert sweep["no_exchange"]["check"] >= len(SWEEP) * 9 // 10
