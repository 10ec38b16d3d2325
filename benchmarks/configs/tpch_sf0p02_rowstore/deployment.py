"""The row-store deployment of TPC-H Q3 is `tpch_sf0p02`'s tables, loader
and plain numpy reference without the columnar replica: one generator and
one reference serve both configurations, so this file re-exports that
module, loaded by its path.  Nothing here imports the program."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "deployment_tpch_tables",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "tpch_sf0p02", "deployment.py"))
_tables = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tables)
globals().update({k: v for k, v in vars(_tables).items() if not k.startswith("__")})

