"""kernels: the exchange and join programs' share of the HBM roofline in a
cell whose statements ride the exchange tier: the bytes that the statements
which ended inside the profiler's window had to read at the DDL's widths
(`deployment.scan_bytes`: for TPC-H Q18 the inner statement's two lineitem
columns, and the outer join's lineitem, orders and customer columns) over
the chip's peak HBM rate, as a share of the device's busy time there.  The
formula is `device_roofline`'s, read from its file; this name reports it in
the cells that `device_roofline` does not list."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "layer_metrics_device_roofline_for_mpp",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "device_roofline.py"))
_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_roofline)


def read(run: dict):
    return _roofline.read(run)
