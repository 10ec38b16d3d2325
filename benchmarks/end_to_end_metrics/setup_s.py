"""Process start to the first timed statement: generate, load, ANALYZE,
replica fill, warm-up and its compiles."""


def read(run: dict):
    return run["setup_s"]
