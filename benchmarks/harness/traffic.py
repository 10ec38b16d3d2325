"""The one traffic generator.  A mix is a data file (`traffic/<mix>.json`):
how many clients, the list of steps that make one operation, and a rule
for every parameter of every step.  The same seed gives the same
statements; every seed gives the same shapes, with other values.

A step is `{"sql": text}` (sent as it is, not compared: BEGIN, COMMIT) or
`{"statement": name, "repeat": n, "once": {param: rule}, "params": {param:
rule}}`, the name being a key of the configuration's `statements.json`;
`once` is drawn one time for the step, `params` anew for each repetition.
`statement` may be a list of names: each repetition sends them one after
the other with one draw of `once` and `params` between them (sysbench's
delete and insert of one table and id).  A rule is a JSON
scalar (a constant), `{"uniform_int": [low, high]}` with both ends
included, `{"choice": [...]}`, `{"plus": [other_param, n]}`, or
`{"sb_string": n}`: n groups of 11 random digits joined by '-', sysbench's
`###########-` template (`oltp_common.lua`: `c` 10 groups, `pad` 5).  An
end of `uniform_int` may be a number or a size of the configuration's
file, with an offset: `"table_size-99"`.  A rule draws from the client's
stream only where a mix names it.

A mix may list `restart_on: [errno, ...]`: an attempt answered with one
of these MySQL error codes is rolled back and the operation starts again
with fresh draws from the same stream, as sysbench restarts an event on
the errors of its `--mysql-ignore-errors` (`run.py`).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

_SIZE = re.compile(r"^([A-Za-z_]\w*)([+-]\d+)?$")


@dataclass
class Step:
    sql: str
    name: str | None = None          # None: a raw step, not compared
    params: dict = field(default_factory=dict)


class Mix:
    def __init__(self, spec: dict, statements: dict, sizes: dict):
        self.spec, self.statements, self.sizes = spec, statements, sizes
        self.clients = int(spec["clients"])
        self.restart_on = frozenset(int(e) for e in spec.get("restart_on", ()))
        if spec.get("loop") != "closed":
            raise ValueError(f"loop {spec.get('loop')!r}: this generator drives closed loops")
        for step in spec["operation"]:
            for name in _names(step):
                if name not in statements:
                    raise KeyError(f"traffic names statement {name!r}; "
                                   f"the configuration has {sorted(statements)}")

    def statement_names(self) -> list:
        seen = []
        for step in self.spec["operation"]:
            seen += [name for name in _names(step) if name not in seen]
        return seen

    def _bound(self, v) -> int:
        if isinstance(v, int):
            return v
        m = _SIZE.match(v)
        if not m or m.group(1) not in self.sizes:
            raise ValueError(f"bound {v!r} is neither a number nor a size of the configuration")
        return int(self.sizes[m.group(1)]) + int(m.group(2) or 0)

    def _draw(self, rules: dict, rng) -> dict:
        out = {}
        for key, rule in rules.items():
            if not isinstance(rule, dict):
                out[key] = rule
            elif "uniform_int" in rule:
                lo, hi = map(self._bound, rule["uniform_int"])
                out[key] = int(rng.integers(lo, hi + 1))
            elif "choice" in rule:
                out[key] = rule["choice"][int(rng.integers(0, len(rule["choice"])))]
            elif "plus" in rule:
                out[key] = out[rule["plus"][0]] + int(rule["plus"][1])
            elif "sb_string" in rule:
                digits = rng.integers(0, 10**11, size=int(rule["sb_string"]))
                out[key] = "-".join(f"{v:011d}" for v in digits.tolist())
            else:
                raise ValueError(f"parameter {key!r}: unknown rule {rule!r}")
        return out

    def operation(self, rng) -> list:
        """The steps of one operation, parameters drawn from `rng`."""
        steps = []
        for step in self.spec["operation"]:
            if "sql" in step:
                steps.append(Step(step["sql"]))
                continue
            once = self._draw(step.get("once", {}), rng)
            for _ in range(int(step.get("repeat", 1))):
                params = {**once, **self._draw(step.get("params", {}), rng)}
                steps += [Step(self.statements[name].format(**params), name, params) for name in _names(step)]
        return steps


def _names(step: dict) -> list:
    """The statement names of a step: none for raw SQL, one, or a list."""
    names = step.get("statement", [])
    return [names] if isinstance(names, str) else list(names)


def client_rng(seed: int, client: int, phase: int):
    """One stream per client and phase (0 warm-up, 1 window)."""
    return np.random.default_rng([int(seed), client, phase])
