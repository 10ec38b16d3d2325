"""Result-corpus harness: EXECUTE the reference's integration-test SQL and
diff the output against the recorded golden results
(ref: /root/reference/tests/integrationtest/run-tests.sh feeding t/*.test to
a real tidb-server and diffing r/*.result; VERDICT r3 missing #3 — the
parser-only replay said nothing about result correctness).

For each .test file: statements execute in order through a fresh Session
(oracle evaluation path — tidb_enable_tpu_coprocessor=OFF, so 47k tiny
statements don't each compile an XLA program; kernel-vs-oracle parity is the
device harness's job), results render mysqltest-style (tab-separated, NULL
literal), and each statement is classified:

  match        executed, output block equals the recorded one
  mismatch     executed, output differs (the real parity debt)
  explain_diff executed EXPLAIN/DESC whose plan rendering differs (this
               engine prints its own plan format, not the reference's
               cost-model tree — tracked separately so the data-parity
               rate is not drowned by plan-format noise)
  error_ok     statement under --error failed as the recording expects
  unsupported  raised a parse/plan/SQL "not supported" class error
  exec_error   raised anything else (engine bug surface)
  desync       the runner lost alignment with the .result echo stream
               (remaining statements in the file are skipped, counted here)

Usage:  python tools/result_corpus.py [--dir PATH] [--files a,b,...] [--per-file]
Prints one JSON line with aggregate counts; per-file detail on stderr.
tests/test_result_corpus.py ratchets the match rate over a pinned file set.
"""

from __future__ import annotations

import json
import os
import re
import sys

# the sweep runs on the CPU: JAX_PLATFORMS is set before JAX is imported
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

TEST_DIR = "/root/reference/tests/integrationtest/t"
RESULT_DIR = "/root/reference/tests/integrationtest/r"

# control directives that carry no SQL and no result lines
_IGNORED_DIRECTIVES = (
    "disable_warnings", "enable_warnings", "disable_info", "enable_info",
    "replace_regex", "replace_column", "begin_concurrent", "end_concurrent",
    "sleep", "real_sleep", "reap", "send",
)


def parse_test(text: str):
    """mysqltest .test -> ordered items.

    ("stmt", [lines], {"sorted": bool, "error": bool}) | ("echo", text)
    Query/result logging directives are tracked via the flags dict returned
    alongside (per-statement snapshot)."""
    items = []
    sorted_next = False
    error_next = False
    qlog = rlog = True
    buf: list[str] = []
    for raw in text.splitlines():
        line = raw.strip()
        if buf:
            buf.append(raw)
            if line.endswith(";"):
                items.append(("stmt", buf, {"sorted": sorted_next, "error": error_next,
                                            "qlog": qlog, "rlog": rlog}))
                buf, sorted_next, error_next = [], False, False
            continue
        if not line:
            continue
        if line.startswith("--"):
            d = line[2:].strip()
            dl = d.lower()
            if dl.startswith("echo"):
                items.append(("echo", d[4:].lstrip()))
            elif dl.startswith("sorted_result"):
                sorted_next = True
            elif dl.startswith("error"):
                error_next = True
            elif dl.startswith("disable_query_log"):
                qlog = False
            elif dl.startswith("enable_query_log"):
                qlog = True
            elif dl.startswith("disable_result_log"):
                rlog = False
            elif dl.startswith("enable_result_log"):
                rlog = True
            # other directives: ignored
            continue
        if line.startswith("#"):
            continue
        low = line.lower()
        if low.startswith(("connect", "connection", "disconnect", "let ", "eval ",
                           "exec ", "source ", "delimiter", "while", "}", "{",
                           "sleep", "vertical_results", "horizontal_results",
                           "inc ", "dec ")):
            continue
        buf.append(raw)
        if line.endswith(";"):
            items.append(("stmt", buf, {"sorted": sorted_next, "error": error_next,
                                        "qlog": qlog, "rlog": rlog}))
            buf, sorted_next, error_next = [], False, False
    return items


def _strip_leading_comments(sql: str) -> str:
    """tpch.test prefixes every query with a /* Qn ... */ block comment."""
    s = sql.lstrip()
    while s.startswith("/*"):
        end = s.find("*/")
        if end < 0:
            break
        s = s[end + 2 :].lstrip()
    return s


def _norm(line: str) -> str:
    return line.rstrip("\r\n")


def _datum_text(d) -> str:
    """Render one datum the way the MySQL client (and mysqltest) prints it."""
    if d.is_null():
        return "NULL"
    v = d.val
    from tidb_tpu.types import DatumKind, MyDecimal

    if d.kind == DatumKind.MysqlJSON:
        from tidb_tpu.types import json_binary as jb

        return jb.to_text(bytes(v)) if hasattr(jb, "to_text") else str(jb.decode(bytes(v)))
    if isinstance(v, (bytes, bytearray)):
        return bytes(v).decode("utf-8", "replace")
    if isinstance(v, float):
        # MySQL prints DOUBLE shortest-roundtrip-ish; repr matches for the
        # common cases, integers drop the .0, exponents drop the '+'
        if v == int(v) and abs(v) < 1e15:
            return str(int(v))
        return repr(v).replace("e+", "e")
    if isinstance(v, MyDecimal):
        return str(v)
    return str(v)


def _results_recode(text: str, session) -> str:
    """Model character_set_results: the server would encode result text into
    the client charset; mysqltest recorded those BYTES into the .result file,
    which this runner reads back as UTF-8-with-replacement. Reproducing the
    same transform makes gbk-session recordings comparable."""
    try:
        cs = session.sysvars.get("character_set_results").lower()
    except Exception:
        return text
    if cs in ("", "utf8", "utf8mb4", "binary"):
        return text
    codec = {"gbk": "gbk", "gb2312": "gb2312", "gb18030": "gb18030",
             "latin1": "latin-1", "ascii": "ascii", "big5": "big5"}.get(cs)
    if codec is None:
        return text
    return text.encode(codec, "replace").decode("utf-8", "replace")


def execute_one(session, sql: str):
    """-> (header_line, row_lines) or raises."""
    res = session.execute(sql)
    if res is None or not getattr(res, "columns", None):
        return None, []
    header = "\t".join(res.columns)
    rows = []
    for r in res.rows:
        text = _results_recode("\t".join(_datum_text(d) for d in r), session)
        # cells may embed newlines (SHOW CREATE TABLE): mysqltest prints
        # them literally, so the recording has them as separate lines
        rows.extend(text.split("\n"))
    return header, rows


UNSUPPORTED_PAT = re.compile(
    r"not supported|unsupported|unknown system variable|no such|not implemented",
    re.I,
)


SAMPLES_CAP = 8


def run_file(name: str, test_dir: str = TEST_DIR, result_dir: str = RESULT_DIR):
    """Execute one corpus file; returns per-class counts + mismatch samples."""
    from tidb_tpu.sql import Session

    test_path = os.path.join(test_dir, name + ".test")
    res_path = os.path.join(result_dir, name + ".result")
    items = parse_test(open(test_path, encoding="utf-8", errors="replace").read())
    rlines = [_norm(x) for x in open(res_path, encoding="utf-8", errors="replace").read().splitlines()]

    s = Session()
    # oracle path: semantics-parity run, no per-shape XLA compiles
    s.sysvars.set("tidb_enable_tpu_coprocessor", "OFF")
    # the reference harness runs each file in a database named after it
    # (run-tests.sh creates DATABASE `$file` and connects to it)
    s.execute(f"create database if not exists `{name}`")
    s.execute(f"use `{name}`")

    counts = {"match": 0, "mismatch": 0, "explain_diff": 0, "error_ok": 0,
              "unsupported": 0, "exec_error": 0, "desync": 0}
    samples: list = []
    cap = SAMPLES_CAP
    cur = 0  # cursor into rlines

    def find_echo(stmt_lines):
        """Locate the echo of this statement at/near the cursor; returns the
        index AFTER the echo, or None. mysqltest may re-wrap long
        statements across lines (tpch.result wraps each CREATE TABLE at
        column boundaries), so an exact line-by-line match is followed by
        a whitespace-normalized multi-line fallback."""
        first = stmt_lines[0].strip()
        want_norm = " ".join(" ".join(stmt_lines).split())
        first_tok = want_norm.split(" ", 1)[0]
        for i in range(cur, min(cur + 200, len(rlines))):
            if rlines[i].strip() == first:
                # multi-line statements echo line by line
                j = i
                ok = True
                for sl in stmt_lines:
                    if j >= len(rlines) or rlines[j].strip() != sl.strip():
                        ok = False
                        break
                    j += 1
                if ok:
                    return j
            # wrapped echo: join result lines until the normalized texts
            # agree (or diverge)
            if rlines[i].strip().startswith(first_tok):
                acc = ""
                for j in range(i, min(i + 80, len(rlines))):
                    acc = (acc + " " + rlines[j].strip()).strip()
                    accn = " ".join(acc.split())
                    if accn == want_norm:
                        return j + 1
                    if not want_norm.startswith(accn):
                        break
        return None

    n_stmt = sum(1 for it in items if it[0] == "stmt")
    stmts = [it for it in items if it[0] == "stmt"]
    seen = 0
    si = -1
    for item_i, it in enumerate(items):
        if it[0] == "echo":
            # the echo may sit past a mismatched statement's recorded block
            # (cur parks at the block start): scan a bounded window so echo
            # lines are consumed instead of polluting the next want-block
            for i in range(cur, min(cur + 400, len(rlines))):
                if rlines[i].strip() == it[1].strip():
                    cur = i + 1
                    break
            continue
        _, stmt_lines, mods = it
        si += 1
        seen += 1
        if not mods["qlog"]:
            counts["desync"] += 1  # unecho'd statements can't be aligned
            continue
        after = find_echo(stmt_lines)
        if after is None:
            # lost alignment: count the rest of the file as desync
            counts["desync"] += n_stmt - seen + 1
            break
        cur = after
        # the recorded output block is EVERYTHING up to the next
        # statement's echo (or EOF) — comparing the full block means a
        # strict-prefix engine result (missing rows) is a MISMATCH, not a
        # match (code-review r4: length-sliced compare inflated the rate)
        # the recorded block ends at the next statement echo OR the next
        # --echo emission, whichever comes first (echo text counted as part
        # of a want-block was the '///// SUBQUERY' phantom-mismatch class)
        block_end = len(rlines)
        nxt_firsts = []
        if si + 1 < len(stmts):
            nxt_firsts.append(stmts[si + 1][1][0].strip())
        for later in items[item_i + 1:]:
            if later[0] == "echo":
                nxt_firsts.append(later[1].strip())
                break
        if nxt_firsts:
            for j in range(cur, min(cur + 400, len(rlines))):
                if rlines[j].strip() in nxt_firsts:
                    block_end = j
                    break
        sql = "\n".join(stmt_lines).strip().rstrip(";")
        expect_error = mods["error"]
        try:
            header, rows = execute_one(s, sql)
            if expect_error:
                # recording expects an error message line(s); resync will
                # handle the echoed error text — classify leniently
                counts["mismatch"] += 1
                continue
            got = ([] if header is None else [header] + rows)
            # ALWAYS compare the full recorded block (to the next echo or
            # EOF): a truncated `want` would count missing trailing rows
            # as a match (code-review r4, twice)
            want = rlines[cur:block_end]
            if mods["sorted"] and header is not None and want:
                got = [got[0]] + sorted(got[1:])
                want = [want[0]] + sorted(want[1:])
            if got == want:
                counts["match"] += 1
                cur += len(got)
            elif _strip_leading_comments(sql).lower().startswith(("explain", "desc")):
                counts["explain_diff"] += 1
            else:
                counts["mismatch"] += 1
                if len(samples) < cap:
                    samples.append({"sql": sql[:120], "got": got[:3], "want": want[:3]})
                # leave `cur` at the echo point; the next find_echo scans
                # forward past this statement's recorded output
        except Exception as exc:  # noqa: BLE001
            from tidb_tpu.parser.parser import ParseError

            if expect_error:
                counts["error_ok"] += 1
                # skip the recorded error-message lines via forward resync
            elif isinstance(exc, ParseError) or UNSUPPORTED_PAT.search(str(exc)):
                # grammar-surface gaps are "unsupported", not engine crashes
                counts["unsupported"] += 1
            else:
                counts["exec_error"] += 1
                if len(samples) < cap:
                    samples.append({"sql": sql[:120], "error": str(exc)[:160]})
    return counts, samples


def run_corpus(files=None, test_dir: str = TEST_DIR, result_dir: str = RESULT_DIR,
               per_file: bool = False):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    if files is None:
        files = sorted(
            fn[:-5] for fn in os.listdir(test_dir)
            if fn.endswith(".test") and os.path.exists(os.path.join(result_dir, fn[:-5] + ".result"))
        )
    total = {"match": 0, "mismatch": 0, "explain_diff": 0, "error_ok": 0,
             "unsupported": 0, "exec_error": 0, "desync": 0}
    details = {}
    for name in files:
        try:
            counts, samples = run_file(name, test_dir, result_dir)
        except Exception as exc:  # noqa: BLE001 — a broken file must not kill the run
            counts, samples = {k: 0 for k in total}, [{"file_error": str(exc)[:200]}]
        for k, v in counts.items():
            total[k] += v
        details[name] = {"counts": counts, "samples": samples}
    executed = sum(total.values()) - total["desync"]
    matched = total["match"] + total["error_ok"]
    rate = matched / executed if executed else 0.0
    non_explain = executed - total["explain_diff"]
    return {
        "files": len(files),
        **total,
        "executed": executed,
        "match_rate": round(rate, 4),
        "data_match_rate": round(matched / non_explain, 4) if non_explain else 0.0,
        "details": details if per_file else None,
    }


def main():
    args = sys.argv[1:]
    files = None
    per_file = False
    test_dir = TEST_DIR
    while args:
        a = args.pop(0)
        if a == "--files":
            files = args.pop(0).split(",")
        elif a == "--per-file":
            per_file = True
        elif a == "--dir":
            test_dir = args.pop(0)
    r = run_corpus(files, test_dir=test_dir, per_file=per_file)
    d = r.pop("details", None)
    print(json.dumps(r))
    if d:
        for name, info in sorted(d.items(), key=lambda kv: -kv[1]["counts"]["mismatch"]):
            c = info["counts"]
            print(f"  {name:40s} match={c['match']:4d} mismatch={c['mismatch']:4d} "
                  f"explain={c['explain_diff']:4d} "
                  f"unsup={c['unsupported']:4d} err={c['exec_error']:4d} desync={c['desync']:4d}",
                  file=sys.stderr)
            for smp in info["samples"][:2]:
                print(f"      {smp}", file=sys.stderr)


if __name__ == "__main__":
    main()
