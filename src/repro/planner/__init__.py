"""Candidate-pruning query planner: filter-and-verify over inverted
postings so selective queries stop sweeping the whole index.

    postings.py  block-compressed hash/buffer-bit postings (128-entry
                 delta-bitpacked or dense-bitmap blocks), incremental
                 under insert
    prune.py     threshold-aware candidate generation with per-block
                 header skipping + packed hits
    plan.py      per-batch dense-vs-pruned cost decision + the host
                 filter-and-verify (+ pruned_topk: upper-bound-pruned
                 top-k)
    device.py    device-resident pruned execution over a SketchArena
                 (block decode → gather-score → packed thresholding
                 with no host round-trip; imported lazily — jax-heavy)
    route.py     the router: the one place a batch's route is decided
                 and run, for ShardedIndex, the api sketch indexes and
                 WindowManager alike (``serve``, ``fused``, ``Routed``;
                 import it directly — it pulls in the arena)

The ragged verify kernel lives with the other Pallas kernels in
:mod:`repro.kernels.gather_score`, the device block-decode/merge in
:mod:`repro.kernels.postings_merge`. ``plan=`` ("auto" | "dense" |
"pruned") reaches the router from every sketch index's
``query``/``batch_query``/``topk``/``serve_batch``.
"""

from repro.planner.plan import (
    PLAN_MODES,
    QueryPlan,
    choose_plan,
    merged_candidates,
    normalize_plan,
    probe_block_stats,
    pruned_batch,
    pruned_topk,
    topk_select,
)
from repro.planner.postings import (
    BLOCK,
    BlockStore,
    PostingsIndex,
    append_rows,
    build_postings,
    decode_blocks,
    decode_store,
    encode_store,
    from_flat,
    postings_equal,
    truncate_postings,
    update_postings,
)
from repro.planner.prune import (
    CandidateSet,
    candidates_for,
    f32_threshold,
    threshold_hits_packed,
)

__all__ = [
    "PLAN_MODES",
    "QueryPlan",
    "choose_plan",
    "merged_candidates",
    "normalize_plan",
    "probe_block_stats",
    "pruned_batch",
    "pruned_topk",
    "topk_select",
    "BLOCK",
    "BlockStore",
    "PostingsIndex",
    "append_rows",
    "build_postings",
    "decode_blocks",
    "decode_store",
    "encode_store",
    "from_flat",
    "postings_equal",
    "truncate_postings",
    "update_postings",
    "CandidateSet",
    "candidates_for",
    "f32_threshold",
    "threshold_hits_packed",
]
