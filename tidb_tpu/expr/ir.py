"""Expression IR — the engine's analog of `tipb.Expr` trees.

The reference serializes planner expressions to protobuf (ref:
pkg/expression/expr_to_pb.go:37 ExpressionsToPBList) and rebuilds them on the
coprocessor side (ref: pkg/expression/distsql_builtin.go). Here the IR *is*
the wire/plan form: immutable, hashable nodes carrying a result FieldType, so
a whole DAG fingerprints to a key for everything whose result depends on
its values (result cache, batch grouping, plan digest), and `seated`
(below, with `exec/dag.py` `DAGRequest.parameterized`) gives the second
identity that compiled XLA programs are keyed by: the plan's shape, with
the statement's literals handed to the program as operands
(SURVEY.md §7 layer 4).

Ops use generic names; the eval class of the *arguments* selects the concrete
semantics at compile time, mirroring how tipb ScalarFuncSig variants
(GTInt/GTReal/GTDecimal/...) are chosen by pkg/expression type inference.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..types import Datum, DatumKind, FieldType, MyDecimal, MyTime

# Canonical op names understood by the compiler (compile.py OP table) and the
# reference evaluator (eval_ref.py). Mirrors the pushdown whitelist idea of
# infer_pushdown.go:160 — anything outside this set cannot be pushed to TPU.
SCALAR_OPS = frozenset(
    {
        # arithmetic
        "plus", "minus", "mul", "div", "intdiv", "mod", "unaryminus", "abs",
        # comparison
        "eq", "ne", "lt", "le", "gt", "ge", "nulleq", "in", "between",
        # logical
        "and", "or", "not", "xor",
        # JSON + regexp (host-only: distsql/root.py HOST_ONLY keeps them
        # at the root oracle; ref: builtin_json_vec.go, builtin_regexp_vec.go)
        "json_extract", "json_unquote", "json_type", "json_valid",
        "json_length", "json_keys", "json_contains", "json_member_of",
        "json_array", "json_object", "json_quote", "regexp", "regexp_like",
        "convert_using",
        # null handling / control
        "isnull", "ifnull", "if", "case", "coalesce",
        # casts (target class from result ft)
        "cast",
        # math
        "ceil", "floor", "round", "sqrt", "exp", "log", "ln", "pow", "sign",
        # string (device subset; packed-word ops)
        "like", "length", "strcmp", "substr",
        "concat", "upper", "lower", "trim", "ltrim", "rtrim", "replace",
        # date/time extraction from packed datetime
        "year", "month", "day", "hour", "minute", "second", "weekday", "to_days", "extract",
        # date arithmetic (unit rides as a const string arg)
        "date_add", "date_sub", "datediff",
        # bit
        "bitand", "bitor", "bitxor", "bitneg", "shiftleft", "shiftright",
    }
)

# host-only custom functions added at runtime by the extension registry
# (ref: pkg/extension custom functions); never device-compiled — the DAG
# splitter pins expressions containing them to the root side
EXTENSION_OPS: set = set()


class Expr:
    """Base expression node. All nodes expose `.ft` and are hashable."""

    __slots__ = ()
    ft: FieldType

    def children(self) -> tuple["Expr", ...]:
        return ()

    def fingerprint(self) -> tuple:
        raise NotImplementedError

    def seated(self, seats: "ParamSeats") -> "Expr":
        """This tree with every parameterisable `Const` replaced by the
        `Param` that `seats` gave it."""
        return self


@dataclass(frozen=True)
class ColumnRef(Expr):
    """Reference to the i-th column of the child operator's output
    (ref: tipb.Expr ColumnRef with offset payload)."""

    index: int
    ft: FieldType

    def fingerprint(self) -> tuple:
        return ("col", self.index, self.ft.tp, int(self.ft.flag), self.ft.flen, self.ft.decimal)


def lane_value(d: Datum, ft: FieldType):
    """The host scalar that stands in every lane of a non-NULL constant of
    a fixed-width class, as `ExprCompiler._const` bakes it and as a `Param`
    hands it over: a float for reals, else an int (decimals scaled by
    10^ft.decimal, times in the packed layout)."""
    et = ft.eval_type()
    if et == "real":
        return float(d.val)
    if et == "decimal":
        dec = d.val if isinstance(d.val, MyDecimal) else MyDecimal(d.val)
        return dec.to_scaled_int(max(ft.decimal, 0))
    if et == "time":
        return d.val.packed if isinstance(d.val, MyTime) else int(d.val)
    return int(d.val)


_PARAM_CLASSES = ("int", "real", "decimal", "time", "string")
_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1
STR_WIDTH_FLOOR = 16


def str_width_rung(nbytes: int) -> int:
    """The byte width a string operand of `nbytes` rides at: the smallest
    rung of a ladder that starts at STR_WIDTH_FLOOR and doubles.  The rung,
    not the bytes, is in the program's key, so literals of one rung share
    a program."""
    w = STR_WIDTH_FLOOR
    while w < nbytes:
        w *= 2
    return w

# The argument positions whose constant the compiler reads while it traces
# (compile.py `_op_round`, `_op_like`, `_date_shift`, `_op_extract`): a
# constant there shapes the program and keeps its value in the program's
# key.  An `_op_*` that comes to need a value at trace time joins this
# table.
TRACE_TIME_ARGS = {"round": (1,), "like": (1,), "date_add": (2,), "date_sub": (2,), "extract": (0,)}


@dataclass(frozen=True)
class Const(Expr):
    """A literal. The datum is part of `fingerprint()`, the identity of
    everything whose result depends on the value. A compiled program does
    not, where the constant is parameterisable (`operand()`): the program's
    key (`DAGRequest.program_key`) holds its type alone and the value is
    handed in as an operand, so statements that differ in such literals
    share one program. NULLs, non-ASCII strings and the positions of
    `TRACE_TIME_ARGS` shape the trace and keep their value in that key."""

    datum: Datum
    ft: FieldType

    def fingerprint(self) -> tuple:
        v = self.datum.val
        key = str(v) if not isinstance(v, (int, float, str, bytes, type(None))) else v
        return ("const", self.datum.kind, key, self.ft.tp, self.ft.decimal)

    def operand(self):
        """(lane, value) where a program takes this constant as an operand,
        lane "i" for the int64 array (ints, scaled decimals, packed times),
        "f" for the float64 one and "s" for the byte rows (strings, the
        value their bytes); None where the constant stays in the trace:
        NULL, a class that rides in no lane, a value that the lane's dtype
        does not hold, or a string with a byte outside ASCII (the CI
        compares fold ASCII alone and screen a constant's bytes while they
        trace, compile.py `_ci_ascii_guard`: such a literal stays where
        they can read it).  The one rule that the program's key and the
        operands are both made from (`seated`)."""
        et = self.ft.eval_type()
        if self.datum.is_null() or et not in _PARAM_CLASSES:
            return None
        if et == "string":
            v = self.datum.val
            b = v.encode() if isinstance(v, str) else bytes(v)   # as compile.py `_const` bakes it
            return ("s", b) if b.isascii() else None
        try:
            v = lane_value(self.datum, self.ft)
        except (TypeError, ValueError, ArithmeticError, AttributeError):
            return None
        if isinstance(v, float):
            return ("f", v)
        return ("i", v) if _I64_MIN <= v <= _I64_MAX else None

    def seated(self, seats: "ParamSeats") -> Expr:
        op = self.operand()
        return self if op is None else seats.seat(self, *op)


@dataclass(frozen=True)
class Param(Expr):
    """A parameterisable constant's seat in a compiled program: slot
    `slot` of the program's int64 (`lane` "i"), float64 ("f") or string
    ("s") operand.  A string seat also holds `width`, the rung of its
    literal's byte length (`str_width_rung`): the widest seat sets the
    operand's row width, a shape of the program.  Exists only inside
    `DAGRequest.parameterized()`'s shape DAG, which is what
    `exec/builder.py` traces and what the program cache keys on."""

    lane: str
    slot: int
    kind: DatumKind
    ft: FieldType
    width: int = 0

    def fingerprint(self) -> tuple:
        # the flag too: signedness picks the compare (compile.py `_cmp`),
        # as a string's collation does
        return ("param", self.lane, self.slot, self.kind, self.ft.tp, int(self.ft.flag), self.ft.decimal,
                self.width, self.ft.collate if self.lane == "s" else None)


class ParamSeats:
    """Hands out `Param` seats in the order of one walk over a DAG and
    keeps the values seated, per lane."""

    __slots__ = ("ints", "floats", "strs")

    def __init__(self):
        self.ints: list = []
        self.floats: list = []
        self.strs: list = []   # bytes

    def seat(self, c: Const, lane: str, value) -> Param:
        vals = self.ints if lane == "i" else self.floats if lane == "f" else self.strs
        vals.append(value)
        return Param(lane, len(vals) - 1, c.datum.kind, c.ft, str_width_rung(len(value)) if lane == "s" else 0)


def seated_all(exprs: tuple, seats: ParamSeats) -> tuple:
    """`seated` over a tuple of expressions."""
    return tuple(e.seated(seats) for e in exprs)


@dataclass(frozen=True)
class ScalarFunc(Expr):
    op: str
    args: tuple
    ft: FieldType

    def __post_init__(self):
        if self.op not in SCALAR_OPS and self.op not in EXTENSION_OPS:
            raise ValueError(f"unknown scalar op {self.op!r}")

    def children(self) -> tuple[Expr, ...]:
        return self.args

    def fingerprint(self) -> tuple:
        return ("fn", self.op, self.ft.tp, int(self.ft.flag), self.ft.decimal) + tuple(
            a.fingerprint() for a in self.args
        )

    def seated(self, seats: ParamSeats) -> Expr:
        fixed = TRACE_TIME_ARGS.get(self.op, ())
        args = tuple(a if i in fixed else a.seated(seats) for i, a in enumerate(self.args))
        return ScalarFunc(self.op, args, self.ft)


# ---- convenience constructors ---------------------------------------------

def col(index: int, ft: FieldType) -> ColumnRef:
    return ColumnRef(index, ft)


def const(d: Datum, ft: FieldType) -> Const:
    return Const(d, ft)


def lit(v, ft: FieldType) -> Const:
    """Build a Const from a python value using the target FieldType."""
    from ..types import DatumKind, MyDecimal, MyTime

    if v is None:
        return Const(Datum.NULL, ft)
    if ft.is_decimal():
        return Const(Datum.dec(MyDecimal(v, max(ft.decimal, 0))), ft)
    if ft.is_float():
        return Const(Datum.f64(float(v)), ft)
    if ft.is_string():
        # keep str subclasses intact (plan-cache slot tags, plancache.SlotStr)
        return Const(Datum.string(v if isinstance(v, str) else str(v)), ft)
    if ft.is_time():
        t = v if isinstance(v, MyTime) else MyTime.parse(str(v), max(ft.decimal, 0))
        return Const(Datum.time(t), ft)
    if ft.is_unsigned():
        return Const(Datum.u64(int(v)), ft)
    return Const(Datum.i64(int(v)), ft)


def func(op: str, ft: FieldType, *args: Expr) -> ScalarFunc:
    return ScalarFunc(op, tuple(args), ft)
