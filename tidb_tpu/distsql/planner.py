"""The ONE execution planner: pick each request's execution tier by data
size and topology (ROADMAP "unify the dispatch path onto the mesh").

Three tiers, one routing seam (ref: the reference picking cop tasks vs
batch-cop vs MPP in planner/core's task-type decision, mpp_gather.go:40):

  single  one region task (or a paging request): the per-task launch path
          with its capacity ladder, retry classification and failpoints.
  pool    N region tasks over the dispatch thread pool, one XLA launch
          per region (the pre-batching shape; also the paging path).
  batch   N tasks grouped per store, stacked on a leading region axis and
          served by ONE vmapped XLA launch per (store, DAG, capacity)
          (PR 4's batch coprocessor).
  mesh    like batch, but the stacked batch is sharded over the device
          mesh under `shard_map` and the per-region PARTIAL AGGREGATE
          STATES are merged ON DEVICE — `jax.lax.psum` over the region
          axis for sum/count/avg states, pmin/pmax for extremes,
          all_gather+local-reduce for bit/first states, a device-side
          merge re-group for GROUP BY tables and a device-side re-top-k
          for TopN — so a store answers with ONE merged state instead of
          R per-region partials for the host to fold (SURVEY §3.1/§5:
          partial/final agg -> psum).

The mesh tier is the paper's north star collective on the STANDARD
`distsql.select` path; the statement-level exchange plans of `mpp/`
(grouped exchange, shuffle joins) ride their own shard_map programs above
this seam. Every tier shares the same up-front epoch checks, typed region
errors, breakers and replica routing — a task can fall from mesh to
batch to single without changing semantics, only launch shape.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..exec.dag import Aggregation, IndexScan, Join, Projection, Selection, TableScan, TopN

# aggregates whose Partial1 states merge with mesh collectives
# (parallel/mesh.py partial_merge_plan: additive states psum, min/max
# pmin/pmax in the right domain, bit/first via all_gather)
MESH_MERGEABLE_AGGS = frozenset({
    "count", "sum", "avg", "min", "max", "first_row",
    "bit_and", "bit_or", "bit_xor",
    "stddev_pop", "stddev_samp", "var_pop", "var_samp",
})

@dataclass(frozen=True)
class TierDecision:
    # per-request tiers: "single" | "pool" | "batch" | "mesh"
    # statement-level tiers (choose_statement_tier): "root" | "mpp"
    tier: str
    # mesh merge kind ("scalar" | "group" | "topn") for the request tiers;
    # exchange plan kind ("agg" | "join") for the statement tiers
    kind: str | None = None


def mesh_merge_kind(dag) -> str | None:
    """Shape gate for the mesh tier: is this pushdown DAG's result
    mergeable ON DEVICE across regions? Returns the merge kind:

      "scalar"  [scan, Sel/Proj/Join*, Aggregation(partial, no GROUP BY)]
                — flat psum/pmin/pmax of the state columns.
      "group"   same with GROUP BY — per-region group tables all_gather
                and re-aggregate in merge mode on device (HashAgg and
                StreamAgg both land here; the merge is always hash).
      "topn"    [scan, Sel/Proj/Join*, TopN] — per-region top-k
                candidates all_gather and re-top-k on device.
      None      ineligible (Complete/Final mode, DISTINCT, group_concat,
                string-valued scalar gather states, Limit/Sort tails,
                reordered output offsets).
    """
    exs = dag.executors
    if len(exs) < 2 or not isinstance(exs[0], (TableScan, IndexScan)):
        return None
    from ..exec.dag import current_schema_fts

    n_out = len(current_schema_fts(exs))
    if tuple(dag.output_offsets) != tuple(range(n_out)):
        # the merge stages index state columns positionally; split_dag's
        # push DAGs always carry identity offsets (root applies the
        # statement's), so anything else is a hand-built DAG — skip
        return None
    if not all(isinstance(e, (Selection, Projection, Join)) for e in exs[1:-1]):
        return None
    last = exs[-1]
    if isinstance(last, TopN):
        return "topn"
    if not isinstance(last, Aggregation) or not last.partial or last.merge:
        return None
    for d in last.aggs:
        if d.distinct or d.name not in MESH_MERGEABLE_AGGS:
            return None
    if last.group_by:
        return "group"
    for d in last.aggs:
        # scalar states ride flat psum lanes; a string-valued gather
        # state (first_row/min/max over varchar) has no lane to ride
        if d.name in ("min", "max", "first_row") and d.ft.is_string():
            return None
    return "scalar"


def _n_devices() -> int:
    import jax

    return len(jax.devices())


def estimated_rows(store) -> int:
    """Coarse data-size signal for the tier decision: the store's live
    key count (MemKV tracks it under its own lock). The authoritative
    check happens store-side on the actually-decoded chunks — this client
    estimate only gates the mesh ATTEMPT, the way the reference's planner
    consults stats before picking an MPP task type."""
    try:
        return len(store.kv)
    except Exception:  # noqa: BLE001 — a stats miss must never fail dispatch
        return 0


def choose_statement_tier(dag, *, allow_mpp: bool, allow_mesh: bool,
                          columnar_routed) -> TierDecision:
    """Statement-level tier pick ABOVE execute_root's per-request tiers
    (ref: mpp_gather.go:40 useMPPExecution — the reference asks "MPP?"
    once per statement before task planning). Returns:

      "mpp"   plan the statement as an exchange-linked fragment graph
              (mpp/dispatch.py): fragment planner + wire seam + columnar
              replica probe sourcing. Joins take this tier even when the
              columnar replica covers the plan — the fragments SOURCE from
              the replica instead of ceding the whole statement to it.
      "root"  execute_root owns dispatch (its own per-request tiers +
              columnar engine routing): tidb_allow_mpp or
              tidb_enable_tpu_mesh OFF, one device, an ineligible shape.

    `columnar_routed` is a thunk so the engine-routing walk only runs when
    the mpp tier is actually on the table."""
    if not allow_mpp or not allow_mesh or _n_devices() < 2:
        return TierDecision("root")
    from ..mpp.fragment import mesh_eligible

    kind = mesh_eligible(dag)
    if kind is None:
        return TierDecision("root")
    # shuffle joins are the mpp tier's raison d'être: the replica serves
    # the probe scan INSIDE the fragment plan, so columnar engine routing
    # does not preempt a join. A grouped agg that the columnar replica owns
    # (engine routing, ISSUE 12) stays with execute_root
    if kind != "join" and columnar_routed():
        return TierDecision("root")
    return TierDecision("mpp", kind)


def choose_tier(store, req, tasks) -> TierDecision:
    """One tier per request (ref: copr task-type selection): paging and
    single-task requests stay on the per-task path; eligible partial-agg /
    TopN shapes with >= 2 devices and enough data ride the mesh; batch_cop
    requests ride the vmapped store batch; everything else the pool."""
    n = len(tasks)
    if n <= 1 or req.paging_size is not None:
        return TierDecision("pool" if (req.concurrency > 1 and n > 1) else "single")
    if req.mesh is not False:
        kind = mesh_merge_kind(req.dag)
        if (
            kind is not None
            and _n_devices() >= 2
            and estimated_rows(store) >= (req.mesh_min_rows or 0)
        ):
            return TierDecision("mesh", kind)
    if req.batch_cop:
        return TierDecision("batch")
    return TierDecision("pool" if req.concurrency > 1 else "single")
