"""Fragment planner — cut an eligible pushdown DAG at exchange boundaries
into ExchangeSender/ExchangeReceiver-linked fragments (ref:
pkg/planner/core/fragment.go:116 GenerateRootMPPTasks; the sender modes are
unistore/cophandler/mpp_exec.go:669-719).

The reference walks the physical plan top-down, starts a new fragment under
every ExchangeReceiver, and assigns each fragment one MPP task per
participating store. Here the cut points are structural — each JOIN
boundary (both sides hash-partition by the join key) and the final-agg
boundary (Partial1 states hash-partition by group key; the Final fragment
streams to root PassThrough) — and the task topology is the mesh itself:
every fragment runs `n_tasks` SPMD tasks, one per device, so the fragment
graph is a launch plan for ONE shard_map program (`mpp/exchange_op.py`)
rather than a process tree. The topology is STABLE: fragment indices are
assigned bottom-up per stage, so equal DAG shapes produce equal plans and
the wire frame (codec/wire.py encode_fragment_plan) round-trips them
byte-exactly.

The string width gate lives here because it is a property of the EXCHANGE,
not of any one tier: packed compare words carry the first
STRING_WORDS*8 bytes across the all_to_all; longer values would silently
truncate, so every exchange plan passes this check. flen counts CHARACTERS
(utf8mb4: up to 4 bytes each) and inserts do not enforce it, so the static
gate is advisory only — the authoritative check measures actual bytes in
the scanned chunks (chunks_exchange_safe).
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from ..exec.dag import Aggregation, DAGRequest, Join, Projection, Selection, TableScan

# exchange partition modes (ref: mpp_exec.go:669 partition types)
EXCHANGE_HASH = "hash"
EXCHANGE_BROADCAST = "broadcast"
EXCHANGE_PASSTHROUGH = "passthrough"

# widest string (bytes) the packed compare words carry byte-exactly
MAX_EXCHANGE_STR = 32

# the root collector pseudo-fragment: the Final fragment's PassThrough
# sender streams to it (ref: the TiDB-side MPPGather above the plan)
ROOT_COLLECTOR = -1


def chunks_exchange_safe(chunks, columns=None) -> bool:
    """No string value in any scanned column (of `columns`, the indices the
    program reads, where given) exceeds the packed-word width the exchange
    can carry byte-exactly."""
    for c in chunks:
        for i, col in enumerate(c.columns):
            if columns is not None and i not in columns:
                continue
            if col.is_varlen() and len(col):
                if int((col.offsets[1:] - col.offsets[:-1]).max()) > MAX_EXCHANGE_STR:
                    return False
    return True


def _refs(exprs) -> set:
    from ..expr.ir import ColumnRef, ScalarFunc

    out: set = set()

    def walk(e):
        if isinstance(e, ColumnRef):
            out.add(e.index)
        elif isinstance(e, ScalarFunc):
            for a in e.args:
                walk(a)

    for e in exprs:
        walk(e)
    return out


def exchange_columns(head: DAGRequest) -> tuple:
    """(probe columns, [build columns] a join stage): the scanned columns
    whose values an exchange plan reads — in a selection, a join key or the
    aggregate — by index into each scan.  The rest of a scan (TPC-H's
    `l_comment` beside Q18's `l_orderkey` and `l_quantity`) is never looked
    at, so its width cannot decide whether the exchange carries the
    statement byte-exactly (`chunks_exchange_safe`)."""
    exs = head.executors
    width = len(exs[0].columns)
    refs: set = set()          # into the schema as the joins widen it
    stages = []                # (offset in the widened schema, width, build's own refs)
    for ex in exs[1:]:
        if isinstance(ex, Selection):
            refs |= _refs(ex.conditions)
        elif isinstance(ex, Join):
            refs |= _refs(ex.probe_keys)
            own = _refs(ex.build_keys)
            for bex in ex.build[1:]:
                own |= _refs(bex.conditions)
            bw = len(ex.build[0].columns)
            stages.append((width, bw, own))
            if ex.join_type not in ("semi", "anti"):
                width += bw
        elif isinstance(ex, Aggregation):
            refs |= _refs(ex.group_by) | _refs([a for d in ex.aggs for a in d.args])
    n_probe = len(exs[0].columns)
    builds = [own | {i - off for i in refs if off <= i < off + bw} for off, bw, own in stages]
    return {i for i in refs if i < n_probe}, builds


@dataclass(frozen=True)
class ExchangeSender:
    """The fragment's output boundary (ref: PhysicalExchangeSender)."""

    exchange_type: str        # EXCHANGE_HASH | _BROADCAST | _PASSTHROUGH
    partition_keys: tuple     # Expr tuple (hash mode; empty otherwise)
    target_fragment: int      # receiving fragment idx (ROOT_COLLECTOR = root)


@dataclass(frozen=True)
class ExchangeReceiver:
    """The fragment's input boundary (ref: PhysicalExchangeReceiver)."""

    source_fragment: int      # fragment whose sender feeds this input


@dataclass(frozen=True)
class Fragment:
    """One exchange-delimited plan slice; runs n_tasks SPMD tasks."""

    idx: int
    executors: tuple          # DAG executor nodes local to this fragment
    receivers: tuple          # ExchangeReceiver inputs, probe side first
    sender: ExchangeSender


@dataclass(frozen=True)
class FragmentPlan:
    fragments: tuple
    n_tasks: int              # tasks per fragment = mesh width
    root: int                 # idx of the Final fragment (streams to root)


def split_tail(dag: DAGRequest) -> tuple:
    """-> (head DAG, tail executors): the DAG cut behind its last
    Aggregation, where everything after it is a Selection (HAVING) or a
    Projection.  The head ends in the Aggregation and outputs its whole
    schema; the tail runs over the final fragment's groups and the DAG's
    output offsets follow it.  No Aggregation, or a tail that orders or
    limits (Sort / TopN / Limit, ROADMAP M9: a tier decision of its own):
    (dag, None)."""
    exs = dag.executors
    last = max((i for i, e in enumerate(exs) if isinstance(e, Aggregation)), default=None)
    if last is None or not all(isinstance(e, (Selection, Projection)) for e in exs[last + 1:]):
        return dag, None
    head = replace(dag, executors=exs[:last + 1],
                   output_offsets=tuple(range(len(exs[last].output_fts()))))
    return head, exs[last + 1:]


def tail_in_program(tail) -> bool:
    """Can the tail be traced behind the final aggregate, in the exchange
    program itself?  The groups leave the exchange with their strings as
    packed compare words and no raw bytes, so a tail expression may pass a
    string column through but compute nothing over strings; and the device
    traces no host-only operator.  Otherwise the root evaluates the tail
    over the exchange's result."""
    from ..distsql.root import host_only_exprs
    from ..expr.ir import ColumnRef, ScalarFunc

    def strings(e) -> bool:
        if isinstance(e, ColumnRef):
            return False
        return e.ft.is_string() or (isinstance(e, ScalarFunc) and any(
            a.ft.is_string() or strings(a) for a in e.args))

    exprs = [c for ex in tail for c in (ex.conditions if isinstance(ex, Selection) else ex.exprs)]
    return not host_only_exprs(exprs) and not any(strings(e) for e in exprs)


def split_join_dag(dag: DAGRequest):
    """-> (probe_scan, pre_sels, [(join, post_sels), ...], agg) or None.

    A CHAIN of shuffle joins is eligible (TPC-H Q3's 3-table shape:
    lineitem ⋈ orders ⋈ customer — each stage re-exchanges the widened
    schema by the next join key, ref: fragment.go stacking ExchangeSender
    under each HashJoin). Build sides must be scan [selection]* — a join
    nested INSIDE a build side still stays off-mesh; the planner
    right-deepens chains so that shape is the common one."""
    exs = dag.executors
    if not exs or not isinstance(exs[0], TableScan):
        return None
    i = 1
    pre = []
    while i < len(exs) and isinstance(exs[i], Selection):
        pre.append(exs[i])
        i += 1
    stages = []
    while i < len(exs) and isinstance(exs[i], Join):
        join = exs[i]
        i += 1
        post = []
        while i < len(exs) and isinstance(exs[i], Selection):
            post.append(exs[i])
            i += 1
        if not join.build or not isinstance(join.build[0], TableScan):
            return None
        if not all(isinstance(e, Selection) for e in join.build[1:]):
            return None
        stages.append((join, post))
    if not stages or i != len(exs) - 1 or not isinstance(exs[i], Aggregation):
        return None
    return exs[0], pre, stages, exs[i]


def _agg_mesh_ok(agg) -> bool:
    if not isinstance(agg, Aggregation) or not agg.group_by or agg.merge:
        return False
    # DISTINCT rides the raw-row exchange (parallel/grouped.py
    # _distinct_exchange_phases); group_concat stays root-only
    return not any(d.name == "group_concat" for d in agg.aggs)


def mesh_eligible(dag: DAGRequest) -> str | None:
    """The exchange-shape gate of the statement tier (ref: the reference's
    per-operator CanPushToTiFlash checks in exhaust_physical_plans).
    Returns the exchange plan kind:

      "agg"  — TableScan [Selection]* Aggregation(GROUP BY)
      "join" — TableScan [Sel]* Join(scan [Sel]*) [Sel]* Aggregation(...)
               (the hash-shuffle repartition join, split_join_dag)
      None   — ineligible (host-only exprs, group_concat, merge mode, an
               ORDER BY / LIMIT tail, ...)

    Either shape may end in a tail of Selections (HAVING) and Projections
    (`split_tail`): it runs behind the final aggregate, in the program
    where `tail_in_program` says so, else at the root."""
    from ..distsql.root import host_only_exprs

    dag, tail = split_tail(dag)
    if tail is None:
        return None
    exs = dag.executors
    if len(exs) < 2 or not isinstance(exs[0], TableScan):
        return None
    agg = exs[-1]
    if not _agg_mesh_ok(agg):
        return None
    agg_exprs = list(agg.group_by) + [a for d in agg.aggs for a in d.args]

    if all(isinstance(e, Selection) for e in exs[1:-1]):
        exprs = [c for e in exs[1:-1] for c in e.conditions] + agg_exprs
        # the device ExprCompiler cannot trace host-only ops (json_*,
        # regexp, extensions) — execute_root keeps them at root, so the
        # exchange program must refuse them rather than fail inside the trace
        return None if host_only_exprs(exprs) else "agg"

    parts = split_join_dag(dag)
    if parts is None:
        return None
    _, pre, stages, _ = parts
    exprs = [c for e in pre for c in e.conditions] + agg_exprs
    for join, post in stages:
        exprs += [c for e in list(join.build[1:]) + post for c in e.conditions]
        exprs += list(join.probe_keys) + list(join.build_keys)
    if host_only_exprs(exprs):
        return None
    return "join"


def fragment_plan(dag: DAGRequest, n_tasks: int) -> FragmentPlan | None:
    """Cut the DAG at its exchange boundaries (fragment.go:116 analog).

    Join shape — per stage i, bottom-up:

        [probe scan frag] --hash(probe key 0)--\\
        [build frag 0]    --hash(build key 0)---> [join frag 0] --hash(...)-> ...
                                ...                [join frag k] --hash(group key)-> [final frag] --passthrough-> root

    Agg shape: [scan+sel+Partial1] --hash(group key)--> [Final] -> root.
    The SAME Aggregation node appears in both agg-boundary fragments: its
    mode (Partial1 vs Final merge) is positional, exactly as the device
    program splits it (grouped.agg_exchange_phases phases 1 and 3). A
    tail (`split_tail`: HAVING, projection) is the Final fragment's, above
    its aggregate, as the reference plans a Selection over the final
    HashAgg inside the fragment that streams to the root."""
    dag, tail = split_tail(dag)
    if tail is None:
        return None
    parts = split_join_dag(dag)
    if parts is not None:
        probe_scan, pre_sels, stages, agg = parts
        frags = []
        n_stages = len(stages)

        def join_frag_idx(i):
            return 2 + 2 * i

        frags.append(Fragment(
            idx=0,
            executors=(probe_scan, *pre_sels),
            receivers=(),
            sender=ExchangeSender(EXCHANGE_HASH, tuple(stages[0][0].probe_keys), join_frag_idx(0)),
        ))
        for i, (join, post_sels) in enumerate(stages):
            frags.append(Fragment(
                idx=2 * i + 1,
                executors=tuple(join.build),
                receivers=(),
                sender=ExchangeSender(EXCHANGE_HASH, tuple(join.build_keys), join_frag_idx(i)),
            ))
            last = i == n_stages - 1
            if last:
                out = ExchangeSender(EXCHANGE_HASH, tuple(agg.group_by), 2 * n_stages + 1)
            else:
                out = ExchangeSender(EXCHANGE_HASH, tuple(stages[i + 1][0].probe_keys), join_frag_idx(i + 1))
            upstream = 0 if i == 0 else join_frag_idx(i - 1)
            frags.append(Fragment(
                idx=join_frag_idx(i),
                executors=(join, *post_sels, *((agg,) if last else ())),
                receivers=(ExchangeReceiver(upstream), ExchangeReceiver(2 * i + 1)),
                sender=out,
            ))
        root_idx = 2 * n_stages + 1
        frags.append(Fragment(
            idx=root_idx,
            executors=(agg, *tail),
            receivers=(ExchangeReceiver(join_frag_idx(n_stages - 1)),),
            sender=ExchangeSender(EXCHANGE_PASSTHROUGH, (), ROOT_COLLECTOR),
        ))
        return FragmentPlan(tuple(frags), n_tasks, root_idx)

    # agg shape: scan [Selection]* Aggregation(GROUP BY)
    exs = dag.executors
    if (len(exs) < 2 or not isinstance(exs[0], TableScan)
            or not isinstance(exs[-1], Aggregation)
            or not all(isinstance(e, Selection) for e in exs[1:-1])):
        return None
    agg = exs[-1]
    if not agg.group_by:
        return None
    frags = (
        Fragment(
            idx=0,
            executors=tuple(exs),
            receivers=(),
            sender=ExchangeSender(EXCHANGE_HASH, tuple(agg.group_by), 1),
        ),
        Fragment(
            idx=1,
            executors=(agg, *tail),
            receivers=(ExchangeReceiver(0),),
            sender=ExchangeSender(EXCHANGE_PASSTHROUGH, (), ROOT_COLLECTOR),
        ),
    )
    return FragmentPlan(frags, n_tasks, 1)
