"""distsql + store cop: join build sides converted and uploaded to the
device, per operation in the window (`COP_AUX_UPLOADS`: misses of the
store's aux-batch cache).  0 says the build tables of a repeated join stay
on the device; 2.0 in TPC-H Q3 would say that `orders` and `customer` go up
again with every statement.  Waits for the counter to be named
(tests/data/q3_counters.json)."""


def read(run: dict):
    n = run["counters"].get("aux_uploads")
    return n / run["attempted"] if n is not None and run["attempted"] else None
