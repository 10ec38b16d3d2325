from .mesh import region_mesh, stack_region_batches, run_sharded_partial_agg
from .grouped import run_sharded_grouped_agg

__all__ = [
    "region_mesh",
    "stack_region_batches",
    "run_sharded_partial_agg",
    "run_sharded_grouped_agg",
]
