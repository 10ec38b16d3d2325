"""distsql + store cop: cross-chip attempts that were declined or failed
and were served by a tier below, per operation in the window
(`MESH_COP_FALLBACKS`: too few rows, skewed lanes, overflow or a failed
launch of the mesh tier; `MPP_FALLBACKS`: the exchange tier's).  A cell that
is there to measure the cross-chip tiers has to read 0.0.  Nothing to read
where the counters are not named (program_names.mesh.json)."""


def read(run: dict):
    c = run["counters"]
    if "mesh_cop_fallbacks" not in c or "mpp_fallbacks" not in c or not run["attempted"]:
        return None
    return (c["mesh_cop_fallbacks"] + c["mpp_fallbacks"]) / run["attempted"]
