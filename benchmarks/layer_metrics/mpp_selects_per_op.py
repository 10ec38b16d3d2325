"""distsql + store cop: statements the exchange tier served (`MPP_SELECTS`:
partial aggregate per region, `all_to_all` by the group key, final
aggregate and tail on each chip), per operation in the window.  In
`tpch_q18_mesh4` every operation's inner GROUP BY ... HAVING is one, so it
has to read 1.0; less says the inner statement ran on another tier.
Nothing to read where the counter is not named (program_names.mesh.json)."""


def read(run: dict):
    c = run["counters"]
    if "mpp_selects" not in c or not run["attempted"]:
        return None
    return c["mpp_selects"] / run["attempted"]
