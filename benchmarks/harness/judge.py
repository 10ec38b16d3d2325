"""The comparison that decides `correct`: every statement that the timed
operations sent, its served rows against the configuration's plain
reference, once the window has closed.  Exact, so every limit is 0."""

from __future__ import annotations

import collections
import json
import statistics

from . import spans

EXAMPLES = 5


class Checker:
    def __init__(self, deployment, data, control: bool = False):
        self.dep, self.data, self.control = deployment, data, control
        self._wanted: dict = {}
        self._control_rows: dict = {}
        self.compared = self.wrong = self.traced_compared = self.traced_wrong = 0
        self.examples: list = []

    def _cached(self, cache: dict, fn, step):
        """`fn` of the statement, once per distinct (statement, literals)."""
        key = (step.name, json.dumps(step.params, sort_keys=True))
        if key not in cache:
            cache[key] = fn(step.name, step.params, self.data)
        return cache[key]

    def _want(self, step):
        return self._cached(self._wanted, self.dep.reference, step)

    def _note(self, where: str, what: str) -> None:
        if len(self.examples) < EXAMPLES:
            self.examples.append(f"{where}: {what}")

    def statement(self, step, rows, where: str) -> None:
        """A plain statement's rows; under `control`, the control's rows
        in their place."""
        if self.control:
            rows = self._cached(self._control_rows, self.dep.control, step)
        self.compared += 1
        diff = self.dep.mismatch(step.name, self._want(step), rows)
        if diff:
            self.wrong += 1
            self._note(where, diff)

    def traced_statement(self, step, tree, where: str) -> None:
        """TRACE returns the span tree in place of the rows: the root
        carries the row count and any error."""
        attrs = tree.get("attrs", {})
        want = self.dep.expected_rows(step.name, self._want(step))
        self.traced_compared += 1
        if "error" in attrs or attrs.get("rows") != want:
            self.traced_wrong += 1
            self._note(where, f"{step.name} traced: {attrs}, want {want} rows")

    def operation(self, op, where: str) -> None:
        for step, answer in zip(op.steps, op.answers):
            if step.name is None:
                continue
            if op.traced:
                self.traced_statement(step, answer, where)
            else:
                self.statement(step, answer, where)
        if op.error:
            self._note(where, f"client {op.client}: {op.error}")

    def window(self, ops: list, unanswered: int) -> dict:
        """Every number compared, beside its limit; `correct` is all of
        them inside."""
        for op in ops:
            self.operation(op, "window")
        failed = sum(op.error is not None for op in ops)
        out = {
            "wrong_answers": {"value": self.wrong, "limit": 0, "of": self.compared},
            "traced_wrong_row_counts": {"value": self.traced_wrong, "limit": 0, "of": self.traced_compared},
            "failed_operations": {"value": failed, "limit": 0, "of": len(ops)},
            "unanswered_clients": {"value": unanswered, "limit": 0},
            "statements_compared": {"value": self.compared, "at_least": 1},
        }
        out["correct"] = (all(r["value"] <= r["limit"] for r in out.values() if "limit" in r)
                          and self.compared >= 1)
        out["examples"] = self.examples
        return out


def statement_medians(ops: list) -> dict:
    """{statement: median client-side ms} over the plain operations."""
    by = collections.defaultdict(list)
    for op in ops:
        if op.traced or op.error:
            continue
        for step, (t0, t1) in zip(op.steps, op.spans):
            by[step.name or step.sql].append((t1 - t0) * 1e3)
    return {k: round(statistics.median(v), 3) for k, v in by.items()}


def self_times_per_op(ops: list) -> dict:
    """{span name: self time, ms per traced operation}."""
    total, n = collections.Counter(), 0
    for op in ops:
        if not op.traced or op.error:
            continue
        n += 1
        for tree in op.answers:
            if tree is not None:
                spans.self_times(tree, total)
    return {k: round(v / n / 1e6, 4) for k, v in total.most_common()} if n else {}
