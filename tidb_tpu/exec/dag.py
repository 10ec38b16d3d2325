"""The DAG request IR — this framework's `tipb.DAGRequest`.

Mirrors the executor-list shape of the reference wire format
(ref: pingcap/tipb DAGRequest; built by pkg/planner/core/plan_to_pb.go and
consumed by unistore/cophandler/cop_handler.go:319 buildDAG): a scan-first
pipeline of executors plus output offsets and encode options. Everything is
immutable and has two identities. `fingerprint()` names the request, values
and tables included: the key of everything whose result depends on them
(ref: the coprocessor-cache keying idea, pkg/store/copr/coprocessor_cache.go).
`DAGRequest.program_key()` names the plan's shape, which is all a compiled
XLA program depends on: every executor's `seated(seats)` gives its copy for
the shape DAG, parameterisable constants replaced by `Param` seats
(expr/ir.py) and the scans' table and index ids dropped, since the rows
arrive as an argument.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import numpy as np

from ..expr.agg import AggDesc
from ..expr.ir import Expr, ParamSeats, seated_all, str_width_rung
from ..types import FieldType


@dataclass(frozen=True)
class ColumnInfo:
    """(ref: tipb.ColumnInfo — column id + type as the scan emits it;
    `default` mirrors tipb's default_val: rows written before an ADD
    COLUMN have no bytes for the column, and the scan fills this origin
    default instead of NULL)."""

    col_id: int
    ft: FieldType
    default: object = None  # Datum | None

    def fingerprint(self):
        d = None if self.default is None else repr(self.default)
        return (self.col_id, self.ft.tp, int(self.ft.flag), self.ft.flen, self.ft.decimal, d)


@dataclass(frozen=True)
class TableScan:
    """(ref: tipb.TableScan; executor mpp_exec.go:110 tableScanExec)."""

    table_id: int
    columns: tuple  # tuple[ColumnInfo, ...]
    desc: bool = False

    def fingerprint(self):
        return ("scan", self.table_id, self.desc) + tuple(c.fingerprint() for c in self.columns)

    def seated(self, seats):
        return replace(self, table_id=0)


@dataclass(frozen=True)
class IndexScan:
    """(ref: tipb.IndexScan; executor mpp_exec.go:255 indexScanExec).

    Reads index entries `t{tid}_i{iid}{vals...}{handle}` instead of rows;
    output schema is the stored entry layout: the indexed columns in index
    order, then the int64 handle (col_id -1). A covering query runs
    entirely off this scan; an index lookup uses it to produce handles for
    a second table read."""

    table_id: int
    index_id: int
    columns: tuple  # tuple[ColumnInfo, ...] — index cols then handle(-1)
    desc: bool = False

    def fingerprint(self):
        return ("iscan", self.table_id, self.index_id, self.desc) + tuple(
            c.fingerprint() for c in self.columns
        )

    def seated(self, seats):
        return replace(self, table_id=0, index_id=0)


@dataclass(frozen=True)
class Selection:
    """(ref: tipb.Selection; mpp_exec.go:1121 selExec)."""

    conditions: tuple  # tuple[Expr, ...]

    def fingerprint(self):
        return ("sel",) + tuple(c.fingerprint() for c in self.conditions)

    def seated(self, seats):
        return Selection(seated_all(self.conditions, seats))


@dataclass(frozen=True)
class Projection:
    """(ref: tipb.Projection; mpp_exec.go:1157 projExec)."""

    exprs: tuple

    def fingerprint(self):
        return ("proj",) + tuple(e.fingerprint() for e in self.exprs)

    def seated(self, seats):
        return Projection(seated_all(self.exprs, seats))


@dataclass(frozen=True)
class Aggregation:
    """(ref: tipb.Aggregation; mpp_exec.go:999 aggExec). Output schema is
    [agg results..., group-by keys...] matching the reference's layout.

    `stream` marks input already sorted by group keys (StreamAgg): the
    boundary-scan kernel runs — no sort, no hash (ops/aggregate.py
    _group_aggregate_stream; ref: agg_stream_executor.go).
    `partial` True emits partial states instead of finalized values.
    """

    group_by: tuple  # tuple[Expr, ...]
    aggs: tuple  # tuple[AggDesc, ...]
    stream: bool = False
    partial: bool = False
    merge: bool = False  # input rows are partial states (Final/Partial2)

    def fingerprint(self):
        return (
            ("agg", self.stream, self.partial, self.merge)
            + tuple(g.fingerprint() for g in self.group_by)
            + tuple(a.fingerprint() for a in self.aggs)
        )

    def seated(self, seats):
        return replace(self, group_by=seated_all(self.group_by, seats),
                       aggs=tuple(a.seated(seats) for a in self.aggs))

    def output_fts(self) -> list[FieldType]:
        out = []
        for a in self.aggs:
            if self.partial:
                out.extend(a.partial_fts())
            else:
                out.append(a.ft)
        out.extend(g.ft for g in self.group_by)
        return out


@dataclass(frozen=True)
class Join:
    """Equi hash join (ref: tipb.Join; unistore/cophandler/mpp_exec.go:844
    joinExec; root-side design pkg/executor/join/hash_join_v2.go:658).

    The enclosing pipeline is the PROBE side (preserved by left_outer, like
    the reference's probe stream); `build` is a scan-first sub-pipeline for
    the build side — its scans consume the request's broadcast aux batches
    (the TiFlash broadcast-exchange analog, mpp_exec.go:669 Broadcast mode).
    Output schema: probe columns ++ build columns (semi/anti: probe only).

    Key expressions must agree in eval class/scale/signedness between the
    two sides — the planner inserts casts, as the reference's hash join
    requires identical key types (join key normalization in planner core).
    """

    build: tuple  # tuple[executor, ...] — scan-first build pipeline
    probe_keys: tuple  # tuple[Expr, ...] over the probe schema
    build_keys: tuple  # tuple[Expr, ...] over the build schema
    join_type: str = "inner"  # inner | left_outer | semi | anti
    # planner-proven: build keys are unique per build row (PK handle or a
    # unique index covering exactly the key columns). The kernel then skips
    # the fan-out expansion pass (output keeps the probe layout); runtime-
    # verified — a fan-out > 1 raises join overflow and the driver retries
    # with the general kernel (ref: hash_join_v2.go one-row-per-key layout).
    build_unique: bool = False

    def __post_init__(self):
        if self.join_type not in ("inner", "left_outer", "semi", "anti"):
            raise ValueError(f"unknown join type {self.join_type!r}")
        if len(self.probe_keys) != len(self.build_keys):
            raise ValueError("join key arity mismatch")

    def fingerprint(self):
        return (
            ("join", self.join_type, self.build_unique)
            + tuple(e.fingerprint() for e in self.build)
            + ("pk",) + tuple(k.fingerprint() for k in self.probe_keys)
            + ("bk",) + tuple(k.fingerprint() for k in self.build_keys)
        )

    def seated(self, seats):
        return replace(self, build=tuple(e.seated(seats) for e in self.build),
                       probe_keys=seated_all(self.probe_keys, seats),
                       build_keys=seated_all(self.build_keys, seats))


@dataclass(frozen=True)
class WinDesc:
    """One window function (ref: tipb.WindowFunc within tipb.Window;
    semantics pkg/executor/aggfuncs/func_{rank,row_number,lead_lag,...}.go).

    `offset` carries the static integer parameter: LEAD/LAG offset,
    NTILE bucket count, NTH_VALUE position. `default` is the lowered
    LEAD/LAG default expression (a Const) or None (NULL)."""

    name: str
    args: tuple  # tuple[Expr, ...] — value argument(s)
    ft: FieldType
    offset: int = 1
    default: object = None  # Expr | None

    def fingerprint(self):
        d = self.default.fingerprint() if self.default is not None else None
        return ("win", self.name, self.offset, d) + tuple(a.fingerprint() for a in self.args)

    def seated(self, seats):
        default = None if self.default is None else self.default.seated(seats)
        return replace(self, default=default, args=seated_all(self.args, seats))


@dataclass(frozen=True)
class Window:
    """(ref: tipb.Window; pkg/executor/window.go WindowExec). Output schema:
    input columns ++ one result column per function — matching the
    reference's appended window result columns (plan_to_pb.go:663)."""

    partition_by: tuple  # tuple[Expr, ...]
    order_by: tuple  # tuple[(Expr, desc: bool), ...]
    funcs: tuple  # tuple[WinDesc, ...]

    def fingerprint(self):
        return (
            ("window",)
            + tuple(e.fingerprint() for e in self.partition_by)
            + ("ord",) + tuple((e.fingerprint(), d) for e, d in self.order_by)
            + ("fn",) + tuple(f.fingerprint() for f in self.funcs)
        )

    def seated(self, seats):
        return Window(seated_all(self.partition_by, seats), _seated_order(self.order_by, seats),
                      tuple(f.seated(seats) for f in self.funcs))


@dataclass(frozen=True)
class TopN:
    """(ref: tipb.TopN; mpp_exec.go:526 topNExec)."""

    order_by: tuple  # tuple[(Expr, desc: bool), ...]
    limit: int

    def fingerprint(self):
        return ("topn", self.limit) + tuple((e.fingerprint(), d) for e, d in self.order_by)

    def seated(self, seats):
        return TopN(_seated_order(self.order_by, seats), self.limit)


@dataclass(frozen=True)
class Sort:
    """Full sort, no bound (ref: tipb.Sort with IsPartialSort=false;
    root executor pkg/executor/sortexec/sort.go — the external merge sort).
    Split shape: each region sorts its rows, the root re-sorts the
    concatenation (the k-way merge specialization can land later —
    correctness first: EVERY row comes back, in order)."""

    order_by: tuple  # tuple[(Expr, desc: bool), ...]

    def fingerprint(self):
        return ("sort",) + tuple((e.fingerprint(), d) for e, d in self.order_by)

    def seated(self, seats):
        return Sort(_seated_order(self.order_by, seats))


@dataclass(frozen=True)
class Limit:
    """(ref: tipb.Limit; mpp_exec.go:397 limitExec)."""

    limit: int

    def fingerprint(self):
        return ("limit", self.limit)

    def seated(self, seats):
        return self


@dataclass(frozen=True)
class DAGRequest:
    """Executor pipeline, scan first (ref: tipb.DAGRequest.Executors).

    output_offsets selects/permutes the final executor's columns
    (ref: cop_handler.go output offsets handling :249-267).
    """

    executors: tuple
    output_offsets: tuple
    time_zone: str = "UTC"
    flags: int = 0

    def fingerprint(self):
        return tuple(e.fingerprint() for e in self.executors) + ("out",) + tuple(self.output_offsets)

    def parameterized(self) -> tuple:
        """(shape DAG, program key, operands): this request split into
        what a compiled program depends on and what it is handed.  One
        walk in executor order (a Join's build pipeline at the Join's
        place) makes all three, so they cannot disagree: the shape DAG has
        `Param` seats where `Const.operand()` says a constant is
        parameterisable and no table or index id; the key is the shape
        DAG's fingerprint; the operands are the seated values as host
        arrays (`_operand_arrays`), an empty lane left out (never Python
        scalars, whose weak types would retrace, and never one array per
        constant).  Kept on the instance: the cache and the driver both
        ask."""
        got = self.__dict__.get("_parameterized")
        if got is None:
            seats = ParamSeats()
            shape = replace(self, executors=tuple(e.seated(seats) for e in self.executors))
            got = (shape, shape.fingerprint(), _operand_arrays(seats))
            object.__setattr__(self, "_parameterized", got)
        return got

    def program_key(self) -> tuple:
        """The identity of the compiled program that serves this request:
        `fingerprint()` without what the program does not depend on."""
        return self.parameterized()[1]

    def program_operands(self) -> tuple:
        """The arguments that follow the batches in a call of that program."""
        return self.parameterized()[2]

    def scan(self):
        assert isinstance(self.executors[0], (TableScan, IndexScan))
        return self.executors[0]

    def output_fts(self) -> list[FieldType]:
        fts = current_schema_fts(self.executors)
        return [fts[i] for i in self.output_offsets]


# which lane of `Param` seats an operand array serves, by its dtype: the
# int64 and float64 values, then the strings' bytes and their lengths
OPERAND_LANES = {"int64": "i", "float64": "f", "uint8": "s", "int32": "n"}


def operand_lanes(operands: tuple) -> tuple:
    return tuple(OPERAND_LANES[o.dtype.name] for o in operands)


def _operand_arrays(seats: ParamSeats) -> tuple:
    """The seated values as host arrays in the order of OPERAND_LANES:
    int64 [slots], float64 [slots], then the strings as zero-padded
    uint8 [slots, W] with int32 [slots] lengths, W being the widest
    seat's rung."""
    out = [np.array(vals, dtype) for vals, dtype in
           ((seats.ints, np.int64), (seats.floats, np.float64)) if vals]
    if seats.strs:
        rows = np.zeros((len(seats.strs), str_width_rung(max(map(len, seats.strs)))), np.uint8)
        for i, b in enumerate(seats.strs):
            rows[i, :len(b)] = np.frombuffer(b, np.uint8)
        out += [rows, np.array([len(b) for b in seats.strs], np.int32)]
    return tuple(out)


def _seated_order(order_by: tuple, seats) -> tuple:
    return tuple((e.seated(seats), d) for e, d in order_by)


def current_schema_fts(executors) -> list[FieldType]:
    """Schema of the last executor's output."""
    fts: list[FieldType] = []
    for ex in executors:
        if isinstance(ex, (TableScan, IndexScan)):
            fts = [c.ft for c in ex.columns]
        elif isinstance(ex, (Selection, Limit, TopN, Sort)):
            pass  # schema unchanged
        elif isinstance(ex, Projection):
            fts = [e.ft for e in ex.exprs]
        elif isinstance(ex, Aggregation):
            fts = ex.output_fts()
        elif isinstance(ex, Window):
            fts = fts + [f.ft for f in ex.funcs]
        elif isinstance(ex, Join):
            if ex.join_type in ("semi", "anti"):
                pass  # probe schema unchanged
            else:
                build_fts = current_schema_fts(ex.build)
                if ex.join_type == "left_outer":
                    build_fts = [f.clone_nullable() for f in build_fts]
                fts = fts + build_fts
        else:
            raise TypeError(f"unknown executor {ex}")
    return fts


def executor_walk(executors) -> list:
    """Executors flattened in execution-summary order: scan first, a Join's
    build pipeline entries before the Join itself — exactly the order the
    fused program appends per-executor row counts."""
    out = [executors[0]]
    for ex in executors[1:]:
        if isinstance(ex, Join):
            out.extend(executor_walk(ex.build))
        out.append(ex)
    return out


def collect_scans(executors) -> list[TableScan]:
    """All TableScans in canonical order: pipeline order, recursing into a
    Join's build side at the Join's position. Device batches (and oracle
    chunks) are supplied in exactly this order."""
    out: list[TableScan] = []
    for ex in executors:
        if isinstance(ex, (TableScan, IndexScan)):
            out.append(ex)
        elif isinstance(ex, Join):
            out.extend(collect_scans(ex.build))
    return out
