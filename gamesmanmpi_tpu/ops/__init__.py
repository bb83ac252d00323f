"""ops: the XLA kernels of the solver.

These are the vmapped/fused replacements for the reference's per-position hot
loops (SURVEY.md §3.5): `expand()`'s one-at-a-time move generation becomes a
batched kernel in each game module; the per-message combine in RESOLVE becomes
ops.combine.combine_children; memo-table lookups become sorted-array
searchsorted in ops.lookup; frontier dedup is ops.dedup.sort_unique.
"""

from gamesmanmpi_tpu.ops.padding import bucket_size, pad_to_bucket
from gamesmanmpi_tpu.ops.dedup import sort_unique
from gamesmanmpi_tpu.ops.lookup import lookup_sorted, lookup_window
from gamesmanmpi_tpu.ops.combine import combine_children
from gamesmanmpi_tpu.ops.provenance import (
    dedup_provenance,
    dedup_provenance_sorted,
)

__all__ = [
    "bucket_size",
    "pad_to_bucket",
    "sort_unique",
    "lookup_sorted",
    "lookup_window",
    "combine_children",
    "dedup_provenance",
    "dedup_provenance_sorted",
]
