"""exec program: string constants handed to programs as operands, per
operation in the window (`PROGRAM_STR_PARAMS_BOUND`).  TPC-H Q3 has one,
its SEGMENT: 1.0 says the five SEGMENTs call one program, 0 that the
literal went back into the program's key.  Waits for the counter to be
named (tests/data/q3_counters.json)."""


def read(run: dict):
    n = run["counters"].get("str_params_bound")
    return n / run["attempted"] if n is not None and run["attempted"] else None
