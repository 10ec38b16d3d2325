"""distsql + store cop: bytes that entered the exchange tier's programs per
operation in the window (`MPP_EXCHANGED_BYTES`: the probe's region chunks
and the build sides, counted before they are partitioned).  Nothing to read
where the counter is not named (program_names.mesh.json)."""


def read(run: dict):
    c = run["counters"]
    if "mpp_exchanged_bytes" not in c or not run["attempted"]:
        return None
    return c["mpp_exchanged_bytes"] / run["attempted"]
