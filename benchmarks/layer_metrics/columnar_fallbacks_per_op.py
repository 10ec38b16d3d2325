"""distsql + store cop / columnar route: statements routed to the columnar
replica that the row store served instead, per operation, in the window.
A cell that reads the replica has to read 0."""


def read(run: dict):
    return run["counters"]["columnar_fallbacks"] / run["attempted"] if run["attempted"] else None
