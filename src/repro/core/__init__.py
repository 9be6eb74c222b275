"""PI core — the paper's contribution: a latch-free batched skip-list index.

Public surface:
  PIConfig, PIIndex, build, empty, execute, lookup, traverse, rebuild,
  maybe_rebuild, range_agg, search/insert/delete_batch   (single shard)
  SearchEngine, get_engine, Probe, BACKENDS, with_backend (descent backends)
  ShardedPIIndex, build_sharded, execute_sharded, make_sharded_executor
  rebalance_from_load / rebalance_from_sample            (NUMA analogue)
  RefIndex                                               (oracle)
"""
from repro.core.batch import SEARCH, INSERT, DELETE, RANGE
from repro.core.engine import BACKENDS, Probe, SearchEngine, get_engine
from repro.core.index import (
    PIConfig, PIIndex, build, empty, execute, execute_impl,
    execute_trace_count, incremental_fits, live_items, lookup, traverse,
    rebuild, maybe_rebuild, needs_rebuild, range_agg, rebuild_if_due, repack,
    search_batch,
    insert_batch, delete_batch, validate_layout, with_backend,
)
from repro.core.distributed import (
    ShardedPIIndex, build_sharded, execute_sharded, make_sharded_executor,
    rebuild_sharded, maybe_rebuild_sharded, maybe_rebuild_shards,
    collect_pairs, dispatch_plan, scatter_to_buffer,
)
from repro.core.rebalance import (
    rebalance_from_load, rebalance_from_sample, load_imbalance,
)
from repro.core.ref import RefIndex

__all__ = [
    "SEARCH", "INSERT", "DELETE", "RANGE", "PIConfig", "PIIndex", "build",
    "empty",
    "execute", "execute_impl", "execute_trace_count", "incremental_fits",
    "live_items", "lookup", "traverse",
    "rebuild", "maybe_rebuild", "needs_rebuild", "range_agg",
    "rebuild_if_due", "repack",
    "search_batch",
    "insert_batch", "delete_batch", "validate_layout", "with_backend",
    "SearchEngine", "get_engine", "Probe", "BACKENDS",
    "ShardedPIIndex", "build_sharded",
    "execute_sharded", "make_sharded_executor", "rebuild_sharded",
    "maybe_rebuild_sharded", "maybe_rebuild_shards", "collect_pairs",
    "dispatch_plan", "scatter_to_buffer",
    "rebalance_from_load", "rebalance_from_sample", "load_imbalance",
    "RefIndex",
]
