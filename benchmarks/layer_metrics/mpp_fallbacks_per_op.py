"""distsql + store cop: exchange-tier attempts that were declined or failed
and were served by a tier below (`MPP_FALLBACKS`: strings wider than the
exchange carries, the capacity ladder run out, a lost dispatch), per
operation in the window.  A cell that is there to measure the exchange tier
has to read 0.0.  Nothing to read where the counter is not named
(program_names.mesh.json)."""


def read(run: dict):
    c = run["counters"]
    if "mpp_fallbacks" not in c or not run["attempted"]:
        return None
    return c["mpp_fallbacks"] / run["attempted"]
