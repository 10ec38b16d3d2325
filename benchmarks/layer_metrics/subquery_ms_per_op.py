"""server + session + planner: ms per traced operation inside
`session.subquery` itself (the span's self time): an uncorrelated IN
subquery's rewrite into the outer statement, around the inner statement's
own spans (its plan, its `distsql.execute_root`, its rows): the set's
dedup and its materialisation as a semi join's build side or literals.
Nothing to read where no operation was traced or the program has no such
span (before PR 38)."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("session.subquery")
