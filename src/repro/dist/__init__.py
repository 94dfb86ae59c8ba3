"""Scale-out subsystem: pluggable collectives, compressed histogram
allreduce, device-sharded sketch construction (DESIGN.md §15).

Supersedes `repro.core.distributed` (kept as a re-export shim). Public
surface:

  * `Collective` + `PsumCollective` / `RingCollective` /
    `HierarchicalCollective`, selected by name via
    `Booster.fit(mesh=, collective=)` or directly via `get_collective`;
    `register_collective` adds strategies to the registry.
  * `CommStats` / `round_comm_stats` — the analytic per-round wire-byte
    and collective-call accounting surfaced on `Booster.comm_stats`.
  * `sharded_sketch_cuts` / `tree_merge` — data-parallel quantile sketch
    build (device-sorted shards, log-depth merge; paper §quantiles).
  * `RoundInputs` / `make_distributed_round` / `make_chunk_runner` — the
    shard_map training round behind `fit(mesh=)`; `make_mesh` builds the
    (Auto-axis) mesh it expects.
"""
from repro.dist.collective import (
    Collective,
    CommStats,
    HierarchicalCollective,
    PsumCollective,
    RingCollective,
    collective_names,
    get_collective,
    register_collective,
    round_comm_stats,
)
from repro.dist.runner import (
    RoundInputs,
    make_chunk_runner,
    make_distributed_round,
    make_mesh,
    train_distributed,
)
from repro.dist.sketch import (
    sharded_sketch_cuts,
    tree_merge,
)

__all__ = [
    "Collective",
    "CommStats",
    "HierarchicalCollective",
    "PsumCollective",
    "RingCollective",
    "RoundInputs",
    "collective_names",
    "get_collective",
    "make_chunk_runner",
    "make_distributed_round",
    "make_mesh",
    "register_collective",
    "round_comm_stats",
    "sharded_sketch_cuts",
    "train_distributed",
    "tree_merge",
]
