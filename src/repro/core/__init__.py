"""The paper's primary contribution: the Kyiv breadth-first minimal
τ-infrequent itemset miner (Demchuk & Leith 2014), in bitset/TPU form, plus
the MINIT baseline and a brute-force oracle."""

from . import exec_cache
from .items import ItemTable, itemize, pack_rows_to_bits, bits_popcount, bits_to_rows
from .placement import (
    BitsetPlacement,
    DevicePlacement,
    HostPlacement,
    MeshPlacement,
    make_placement,
    resolve_interpret,
    resolve_placement,
)
from .preprocess import Preprocessed, preprocess, ORDERINGS
from .prefix import (
    Level,
    CandidateBatch,
    generate_candidates,
    group_reps,
    iter_group_spans,
    prefix_group_sizes,
)
from .support import ItemsetIndex, support_test
from .bounds import lemma_bound, corollary_bound, apply_bounds
from .frontier import LevelFrontier, mine_levels
from .kyiv import (
    KyivConfig,
    LevelStats,
    MiningResult,
    MiningState,
    mine,
    mine_preprocessed,
    prepare,
)
from .oracle import brute_force_minimal_infrequent
from .minit import minit_minimal_infrequent

__all__ = [
    "exec_cache",
    "ItemTable",
    "itemize",
    "pack_rows_to_bits",
    "bits_popcount",
    "bits_to_rows",
    "BitsetPlacement",
    "HostPlacement",
    "DevicePlacement",
    "MeshPlacement",
    "make_placement",
    "resolve_interpret",
    "resolve_placement",
    "Preprocessed",
    "preprocess",
    "ORDERINGS",
    "Level",
    "CandidateBatch",
    "generate_candidates",
    "group_reps",
    "iter_group_spans",
    "prefix_group_sizes",
    "LevelFrontier",
    "mine_levels",
    "ItemsetIndex",
    "support_test",
    "lemma_bound",
    "corollary_bound",
    "apply_bounds",
    "KyivConfig",
    "LevelStats",
    "MiningResult",
    "MiningState",
    "mine",
    "mine_preprocessed",
    "prepare",
    "brute_force_minimal_infrequent",
    "minit_minimal_infrequent",
]
