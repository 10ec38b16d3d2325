"""distsql + store cop: ms per traced operation inside `cop.decode` itself
(the span's self time): the region's rows read from the row store, decoded
into columns and handed to the device as a batch, before the program is
called.  Nothing to read where no operation was traced or none decoded."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("cop.decode")
