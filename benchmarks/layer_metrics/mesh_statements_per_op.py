"""distsql + store cop: statements answered by one cross-chip program, per
operation in the window: launches of the per-request mesh tier
(`MESH_COP_BATCHES`: the region lanes of a store sharded over the chips,
partial states merged on the device) plus statements the exchange tier
served (`MPP_SELECTS`).  In `tpch_q1q6q3_mesh4` every statement is one such
program, so it has to read 3.0; less says a statement ran on one chip.
Nothing to read where the counters are not named (program_names.mesh.json)."""


def read(run: dict):
    c = run["counters"]
    if "mesh_cop_batches" not in c or "mpp_selects" not in c or not run["attempted"]:
        return None
    return (c["mesh_cop_batches"] + c["mpp_selects"]) / run["attempted"]
