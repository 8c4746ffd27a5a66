"""KV-pool planner: CAM's cost model sizing a paged KV block pool."""
from repro.serve import kv_cache, planner

__all__ = ["kv_cache", "planner"]
