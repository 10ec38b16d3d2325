"""kernels: the bytes that the statements which ended inside the
profiler's window had to read (logical widths, kept with the
configuration) over the chip's peak HBM rate, as a share of the device's
busy time there.  Bound: HBM.  Nothing to read where a statement's bytes
are not kept, where the device kind has no peak, or where nothing ran."""


def read(run: dict):
    p = run.get("profile")
    if not p or not p["needed_bytes"] or not p["peaks"] or p["busy_s"] <= 0:
        return None
    return 100.0 * p["needed_bytes"] / p["peaks"]["hbm_bytes_per_s"] / p["busy_s"]
