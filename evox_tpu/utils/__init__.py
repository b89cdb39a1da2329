from .common import (
    TreeAndVector,
    parse_opt_direction,
    rank_based_fitness,
    standardise,
    min_by,
    compose,
    pairwise_euclidean_dist,
    pairwise_manhattan_dist,
    pairwise_chebyshev_dist,
    cos_dist,
    dominate_relation,
    new_key,
    frames2gif,
)
from .aggregation import AggregationFunction
from .io import to_x32_if_needed, x32_func_call
from .optimizers import clipup, make_optimizer
from .compile_cache import enable_compile_cache

__all__ = [
    "TreeAndVector",
    "parse_opt_direction",
    "rank_based_fitness",
    "standardise",
    "min_by",
    "compose",
    "pairwise_euclidean_dist",
    "pairwise_manhattan_dist",
    "pairwise_chebyshev_dist",
    "cos_dist",
    "dominate_relation",
    "frames2gif",
    "to_x32_if_needed",
    "x32_func_call",
    "new_key",
    "AggregationFunction",
    "clipup",
    "make_optimizer",
    "enable_compile_cache",
]
