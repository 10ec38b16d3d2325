"""All operations done inside the window over the window's length.  An
operation still in flight when the window closed is waited for and counts
by the share of its time that lay inside."""


def read(run: dict):
    return run["operations"] / run["seconds"]
