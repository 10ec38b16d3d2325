"""`jax-audit` — trace the exec builder's compiled programs to closed
jaxprs and walk them for device-hostile patterns (ISSUE 9; ref: the
reference audits its pushed-down executors with plan tests — here the
"plan" is the jaxpr XLA will compile, so the audit walks that).

A catalog of representative DAG programs — one per exec-op builder path
(selection, hash aggregation, stream aggregation, topn, hash join), each
traced BOTH single-region and vmap-batched — goes through four checks:

  * **float64 leaks** — the catalog's columns are all integers, so any
    f64/c128 appearing in the jaxpr was INTRODUCED by the program (a
    Python float promotion, a stray true-divide, an astype): on TPU that
    means software-emulated arithmetic on the hot path. Programs with
    real DOUBLE columns legitimately carry f64 (MySQL semantics); the
    audit pins the *int-only* programs where any f64 is a leak.
  * **host callbacks / transfers inside jit** — pure_callback and
    friends serialize every launch through the host; device_put inside a
    traced program is a transfer the donor should have done outside.
  * **vmap axis consistency** — every output of the region-batched
    variant must carry the leading region axis (size B) over the single
    variant's shape with the same dtype; a dropped/reordered axis means
    region results silently alias each other.
  * **trace stability** — building the same program twice must produce
    byte-identical jaxprs. A closure-captured Python scalar (a counter,
    a timestamp, an id()) bakes a different constant each build: every
    ProgramCache miss then compiles a NEW entry (the cache key can't see
    the closure), silently multiplying entries and compile time. Large
    baked consts (>4 KiB) are flagged for the same reason: operand data
    belongs in arguments, not in the program.  The same holds for a
    statement's literals: every catalog program is also built for a
    second set of literal values, and the two jaxprs must be
    byte-identical — a parameterisable literal is an operand of the
    program (`DAGRequest.program_operands`), and one baked into the
    trace would compile a program per fresh literal.

Fixture mode (`--files`): a fixture module exports `JAX_AUDIT_CATALOG`,
a list of `{"name": str, "make": callable}` entries where `make()`
returns `(fn, args)`; each is traced through the same checks.  An entry
may add `"make_other"`: the same program built for other literal values.
"""

from __future__ import annotations

import importlib.util
import os
import sys

from .common import Finding

PASS = "jax-audit"

# where live findings anchor: the program builder is the artifact under audit
_BUILDER_REL = os.path.join("tidb_tpu", "exec", "builder.py")

_HOST_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
    "device_put",
}

_CONST_LIMIT_BYTES = 4096

_VMAP_BATCH = 3
_CAPACITY = 8
_RADIX_CAPACITY = 512  # probe capacity satisfying the radix ratio gate
_GROUP_CAPACITY = 16


# ----------------------------------------------------------- jaxpr walking

def iter_eqns(jaxpr):
    """Every eqn in a (closed) jaxpr, recursing through call primitives
    (pjit/closed_call), scan/while carries and cond branches."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            yield from _iter_sub(p)


def _iter_sub(p):
    if hasattr(p, "eqns"):  # a Jaxpr
        yield from iter_eqns(p)
    elif hasattr(p, "jaxpr"):  # a ClosedJaxpr
        yield from iter_eqns(p.jaxpr)
    elif isinstance(p, (list, tuple)):
        for q in p:
            yield from _iter_sub(q)


def _avals_of(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        av = getattr(v, "aval", None)
        if av is not None and hasattr(av, "dtype"):
            yield av


def _wide_float(dtype) -> bool:
    s = str(dtype)
    return s in ("float64", "complex128")


def audit_jaxpr(name: str, closed, anchor: tuple) -> list:
    """f64-leak + host-callback checks over one closed jaxpr. `anchor`
    is the (rel, line) findings attach to."""
    rel, line = anchor
    findings: list = []
    f64_prims: dict = {}
    host_prims: dict = {}
    for eqn in iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        if pname in _HOST_PRIMITIVES:
            host_prims.setdefault(pname, 0)
            host_prims[pname] += 1
        for av in _avals_of(eqn):
            if _wide_float(av.dtype):
                f64_prims.setdefault(pname, 0)
                f64_prims[pname] += 1
                break
    # leaks only count when no INPUT carried the wide type (real DOUBLE
    # columns legitimately flow f64 end to end)
    in_wide = any(_wide_float(getattr(av, "dtype", ""))
                  for av in closed.in_avals if hasattr(av, "dtype"))
    if f64_prims and not in_wide:
        prims = ", ".join(sorted(f64_prims))
        findings.append(Finding(
            rel, line, PASS,
            f"program {name!r}: float64 leaked into an integer-only program "
            f"(primitives: {prims}) — on TPU this is software-emulated math; "
            f"find the Python float / true-divide / astype that promoted"))
    for pname, n in sorted(host_prims.items()):
        findings.append(Finding(
            rel, line, PASS,
            f"program {name!r}: host primitive `{pname}` x{n} inside the "
            f"jitted program — every launch round-trips through the host; "
            f"hoist it out of the traced computation"))
    for i, c in enumerate(getattr(closed, "consts", ()) or ()):
        nbytes = getattr(c, "nbytes", 0)
        if nbytes and nbytes > _CONST_LIMIT_BYTES:
            findings.append(Finding(
                rel, line, PASS,
                f"program {name!r}: baked constant #{i} is {nbytes} bytes — "
                f"closure-captured operand data recompiles (and re-uploads) "
                f"per build; pass it as a program argument instead"))
    return findings


def audit_stability(name: str, make, anchor: tuple, make_other=None) -> tuple:
    """Trace `make()` twice; differing jaxprs mean a closure-captured
    value changed between builds.  `make_other()` builds the program for
    other literal values; a jaxpr that differs from the first means a
    literal was baked into the trace.  Returns (findings,
    first_closed_jaxpr, args) so callers reuse the trace."""
    import jax

    rel, line = anchor
    fn1, args1 = make()
    fn2, args2 = make()
    jx1 = jax.make_jaxpr(fn1)(*args1)
    jx2 = jax.make_jaxpr(fn2)(*args2)
    findings: list = []
    if str(jx1) != str(jx2):
        findings.append(Finding(
            rel, line, PASS,
            f"program {name!r}: two identical builds traced to DIFFERENT "
            f"jaxprs — a closure-captured Python scalar (counter, timestamp, "
            f"id) is baked into the trace; every build multiplies "
            f"ProgramCache entries with programs the cache key cannot tell "
            f"apart"))
    if make_other is not None:
        fn3, args3 = make_other()
        if str(jx1) != str(jax.make_jaxpr(fn3)(*args3)):
            findings.append(Finding(
                rel, line, PASS,
                f"program {name!r}: builds for two different literal values "
                f"traced to DIFFERENT jaxprs — a literal is baked into the "
                f"trace instead of riding as an operand, so every fresh "
                f"literal traces and compiles a program of its own"))
    return findings, jx1, args1


# ----------------------------------------------------------- live catalog

def _int_chunk(n: int = 6):
    from ..chunk import Chunk
    from ..types import Datum, new_longlong

    I = new_longlong()
    rows = [[Datum.i64(i % 3), Datum.i64(i * 7 % 11)] for i in range(n)]
    return Chunk.from_rows([I, I], rows), I


def _scan(table_id: int, I):
    from ..exec.dag import ColumnInfo, TableScan

    return TableScan(table_id, (ColumnInfo(1, I), ColumnInfo(2, I)))


def live_catalog(literal: int = 2) -> list:
    """(name, dag, n_batches) for every exec-op builder path — the
    acceptance set: selection, hashagg, streamagg, topn, hashjoin.
    `literal` is the value of the catalog's parameterisable constants:
    the trace-stability check builds every program for two of them."""
    from ..exec.dag import Aggregation, ColumnInfo, DAGRequest, Join, Selection, TableScan, TopN
    from ..expr import AggDesc, col, func, lit

    _ch, I = _int_chunk()
    scan = _scan(31, I)
    sel = DAGRequest(
        (scan, Selection((func("gt", I, col(1, I), lit(literal, I)),))),
        output_offsets=(0, 1))
    hashagg = DAGRequest(
        (scan, Aggregation(group_by=(col(0, I),),
                           aggs=(AggDesc("sum", (col(1, I),)),
                                 AggDesc("count", (col(1, I),))))),
        output_offsets=(0, 1, 2))
    streamagg = DAGRequest(
        (scan, Aggregation(group_by=(col(0, I),),
                           aggs=(AggDesc("max", (col(1, I),)),), stream=True)),
        output_offsets=(0, 1))
    # a literal in the order expression: the mesh variant's re-top-k
    # compiles it a second time, outside the per-region pipeline
    topn = DAGRequest(
        (scan, TopN(order_by=((func("mod", I, col(1, I), lit(literal + 5, I)), True),), limit=4)),
        output_offsets=(0, 1))
    join = DAGRequest(
        (scan, Join(build=(_scan(32, I),), probe_keys=(col(0, I),),
                    build_keys=(col(0, I),), join_type="inner")),
        output_offsets=(0, 1, 2, 3))
    # the radix-partitioned join path (ISSUE 13): planner-proven unique
    # build + int keys routes through ops/radix_join.py when the
    # build/probe capacity ratio passes — the probe batch is padded wide
    # (RADIX_CAPACITY) so the gate holds at catalog scale; the grouped
    # tail makes the mesh variant ("group" kind) trace too
    radix_join = DAGRequest(
        (TableScan(33, (ColumnInfo(1, I), ColumnInfo(2, I))),
         Join(build=(_scan(34, I),), probe_keys=(col(0, I),),
              build_keys=(col(0, I),), join_type="inner",
              build_unique=True),
         Aggregation(group_by=(col(1, I),),
                     aggs=(AggDesc("sum", (col(2, I),)),), partial=True)),
        output_offsets=(0, 1))
    # partial-mode shapes: what the dispatch planner's MESH tier runs —
    # audited as shard_map programs too (mesh_merge_kind gates which)
    partial_scalar = DAGRequest(
        (scan, Aggregation(group_by=(),
                           aggs=(AggDesc("sum", (col(1, I),)),
                                 AggDesc("count", ())), partial=True)),
        output_offsets=(0, 1))
    partial_hashagg = DAGRequest(
        (scan, Aggregation(group_by=(col(0, I),),
                           aggs=(AggDesc("sum", (col(1, I),)),
                                 AggDesc("count", ())), partial=True)),
        output_offsets=(0, 1, 2))
    # the columnar-replica scan shape (ISSUE 12): the WHOLE logical plan
    # — scan -> selection -> complete aggregation — runs as one program
    # over the replica's device-resident stable chunk (columnar/route.py
    # `_run`), no partial/final split, no region axis
    columnar_scan = DAGRequest(
        (scan, Selection((func("gt", I, col(1, I), lit(literal, I)),)),
         Aggregation(group_by=(col(0, I),),
                     aggs=(AggDesc("sum", (col(1, I),)),
                           AggDesc("count", ())))),
        output_offsets=(0, 1, 2))
    return [
        ("selection", sel, 1, None),
        ("hashagg", hashagg, 1, None),
        ("streamagg", streamagg, 1, None),
        ("topn", topn, 1, None),
        ("hashjoin", join, 2, None),
        # probe batch padded wide so the radix build/probe ratio gate
        # holds — the trace goes through ops/radix_join.py, not the
        # monolithic kernel (assert: its program carries no 4-operand
        # merge sort; the audit checks f64/host/consts/stability)
        ("radix_join", radix_join, 2, (_RADIX_CAPACITY, _CAPACITY)),
        ("partial_scalar_agg", partial_scalar, 1, None),
        ("partial_hashagg", partial_hashagg, 1, None),
        ("columnar_scan", columnar_scan, 1, None),
    ]


def _entry_caps(n_batches: int, caps) -> tuple:
    return tuple(caps) if caps else tuple(_CAPACITY for _ in range(n_batches))


def _batches(n_batches: int, vmap: bool, caps=None):
    from ..chunk import to_device_batch
    from ..chunk.device import to_stacked_device_batch

    caps = _entry_caps(n_batches, caps)
    ch, _I = _int_chunk()
    if vmap:
        probe = to_stacked_device_batch([ch] * _VMAP_BATCH, caps[0])
    else:
        probe = to_device_batch(ch, capacity=caps[0])
    aux = [to_device_batch(ch, capacity=c) for c in caps[1:]]
    return [probe] + aux


def _make_builder(dag, n_batches: int, vmap: bool, caps=None):
    """A `make` thunk for audit_stability: a fresh build_program each
    call — exactly what a ProgramCache miss does."""
    from ..exec.builder import build_program

    def make():
        cd = build_program(
            dag, _entry_caps(n_batches, caps),
            group_capacity=_GROUP_CAPACITY,
            vmap_batch=_VMAP_BATCH if vmap else None)
        make.outputs = cd.outputs  # of the last build: `leaf_avals()` once the build was traced
        return cd.fn, _batches(n_batches, vmap, caps) + list(dag.program_operands())
    return make


_LIVE_MEMO: list | None = None


def audit_live() -> list:
    """Trace the whole catalog (single + vmapped) through every check.
    Memoized per process — the catalog is deterministic and the traces
    are the expensive part."""
    global _LIVE_MEMO
    if _LIVE_MEMO is not None:
        return list(_LIVE_MEMO)
    anchor = (_BUILDER_REL.replace(os.sep, "/"), 1)
    findings: list = []
    import jax

    other_literals = {n: d for n, d, _nb, _caps in live_catalog(literal=7)}
    for name, dag, n_batches, caps in live_catalog():
        single_out = None
        for vmap in (False, True):
            variant = f"{name}/{'vmap' if vmap else 'single'}"
            make = _make_builder(dag, n_batches, vmap, caps)
            try:
                if vmap:
                    # the stability double-build already ran on the single
                    # variant (same builder, same closures) — the vmapped
                    # trace runs once, for the axis + jaxpr checks
                    fn, args = make()
                    closed = jax.make_jaxpr(fn)(*args)
                    fs = []
                else:
                    fs, closed, _args = audit_stability(
                        variant, make, anchor,
                        make_other=_make_builder(other_literals[name], n_batches, vmap, caps))
            except Exception as exc:  # noqa: BLE001 — a trace failure IS a finding
                findings.append(Finding(
                    anchor[0], anchor[1], PASS,
                    f"program {variant!r} failed to trace: {exc}"))
                continue
            findings.extend(fs)
            findings.extend(audit_jaxpr(variant, closed, anchor))
            # the region axis is checked on the leaves the host reads, before
            # the program's epilogue lays them into its one byte buffer
            if not vmap:
                single_out = make.outputs.leaf_avals()
            else:
                findings.extend(_check_vmap_axis(name, single_out, make.outputs.leaf_avals(), anchor))
        findings.extend(_audit_mesh_variant(name, dag, n_batches, anchor, caps))
    findings.extend(_audit_exchange_variant(anchor))
    _LIVE_MEMO = list(findings)
    return findings


def _audit_exchange_variant(anchor) -> list:
    """Trace the MPP exchange-join shard_map shape (ISSUE 18): the
    shuffle-join chain — hash-partition both sides, all_to_all, local
    join, grouped agg phases — as ONE program (mpp/exchange_op.py
    `exchange_join_program`), walked through the same f64/host-callback/
    const jaxpr checks; iter_eqns recurses the shard_map body."""
    import jax

    from ..exec.dag import Aggregation, DAGRequest, Join
    from ..expr import AggDesc, col
    from ..mpp.exchange_op import exchange_join_program
    from ..parallel.mesh import region_mesh, stack_region_batches

    _ch, I = _int_chunk()
    dag = DAGRequest(
        (_scan(41, I),
         Join(build=(_scan(42, I),), probe_keys=(col(0, I),),
              build_keys=(col(0, I),), join_type="inner"),
         Aggregation(group_by=(col(1, I),),
                     aggs=(AggDesc("sum", (col(2, I),)),
                           AggDesc("count", ())))),
        output_offsets=(0, 1, 2))
    variant = "exchange_join/mesh"
    try:
        n_dev = len(jax.devices())
        mesh = region_mesh(n_dev)
        ch, _I = _int_chunk()
        stacked_p = stack_region_batches([ch] * n_dev, n_total=n_dev)
        stacked_b = stack_region_batches([ch] * n_dev, n_total=n_dev)
        fn = exchange_join_program(dag, mesh, group_capacity=_GROUP_CAPACITY)
        closed = jax.make_jaxpr(fn)(stacked_p, stacked_b)
    except Exception as exc:  # noqa: BLE001 — a trace failure IS a finding
        return [Finding(anchor[0], anchor[1], PASS,
                        f"program {variant!r} failed to trace: {exc}")]
    return audit_jaxpr(variant, closed, anchor)


def _audit_mesh_variant(name: str, dag, n_batches: int, anchor, caps=None) -> list:
    """Trace the MESH-tier shard_map variant (on-device psum of the
    batched partials) for every catalog shape the dispatch planner would
    route there, and walk its jaxpr through the same f64/host-callback/
    const checks — iter_eqns recurses the shard_map body like any other
    sub-jaxpr. Devices: whatever this process has (1 in the CLI, 8 under
    the test mesh) — the program specializes to the count either way."""
    import jax

    from ..distsql.planner import mesh_merge_kind
    from ..exec.builder import build_program

    kind = mesh_merge_kind(dag)
    if kind is None:
        return []
    variant = f"{name}/mesh-{kind}"
    entry_caps = _entry_caps(n_batches, caps)
    n_dev = min(len(jax.devices()), _VMAP_BATCH)
    lanes = -(-_VMAP_BATCH // n_dev) * n_dev
    try:
        cd = build_program(
            dag, entry_caps,
            group_capacity=_GROUP_CAPACITY,
            mesh_lanes=lanes, mesh_devices=n_dev, mesh_kind=kind)
        from ..chunk.device import to_stacked_device_batch

        ch, _I = _int_chunk()
        stacked = to_stacked_device_batch([ch] * lanes, entry_caps[0])
        aux = _batches(n_batches, False, caps)[1:]
        closed = jax.make_jaxpr(cd.fn)(stacked, *aux, *dag.program_operands())
    except Exception as exc:  # noqa: BLE001 — a trace failure IS a finding
        return [Finding(anchor[0], anchor[1], PASS,
                        f"program {variant!r} failed to trace: {exc}")]
    return audit_jaxpr(variant, closed, anchor)


def _check_vmap_axis(name: str, single_avals, vmap_avals, anchor) -> list:
    rel, line = anchor
    if single_avals is None:
        return []
    if len(single_avals) != len(vmap_avals):
        return [Finding(rel, line, PASS,
                        f"program {name!r}: vmapped variant has {len(vmap_avals)} "
                        f"outputs vs {len(single_avals)} single — outputs dropped "
                        f"or added along the region axis")]
    out: list = []
    for i, (s, v) in enumerate(zip(single_avals, vmap_avals)):
        ss = tuple(getattr(s, "shape", ()))
        vs = tuple(getattr(v, "shape", ()))
        if vs != (_VMAP_BATCH,) + ss or str(getattr(s, "dtype", "")) != str(getattr(v, "dtype", "")):
            out.append(Finding(
                rel, line, PASS,
                f"program {name!r}: output #{i} rank/dtype inconsistent along "
                f"the region axis — single {ss}/{getattr(s, 'dtype', '?')} vs "
                f"vmapped {vs}/{getattr(v, 'dtype', '?')} (expected "
                f"{(_VMAP_BATCH,) + ss} with the same dtype)"))
    return out


# ----------------------------------------------------------- fixture mode

def _load_fixture_catalog(sf):
    spec = importlib.util.spec_from_file_location(
        f"_jaxaudit_fixture_{abs(hash(sf.path))}", sf.path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.modules.pop(spec.name, None)
    return getattr(mod, "JAX_AUDIT_CATALOG", [])


def audit_files(files) -> list:
    findings: list = []
    for sf in files:
        if "JAX_AUDIT_CATALOG" not in getattr(sf, "text", ""):
            continue  # never import modules that don't opt in — fixture
            # files for OTHER passes may have import side effects
        try:
            catalog = _load_fixture_catalog(sf)
        except Exception:  # noqa: BLE001 — non-catalog fixture files
            continue
        for entry in catalog:
            name = entry["name"]
            make = entry["make"]
            anchor = (sf.rel, entry.get("line", 1))
            try:
                fs, closed, _args = audit_stability(name, make, anchor, entry.get("make_other"))
            except Exception as exc:  # noqa: BLE001
                findings.append(Finding(
                    sf.rel, entry.get("line", 1), PASS,
                    f"program {name!r} failed to trace: {exc}"))
                continue
            findings.extend(fs)
            findings.extend(audit_jaxpr(name, closed, anchor))
    return findings


def run(files=None) -> list:
    """Vet-pass entry point: no `files` = the live builder catalog;
    explicit files = fixture catalogs (`JAX_AUDIT_CATALOG` modules)."""
    if files:
        return audit_files(files)
    return audit_live()
