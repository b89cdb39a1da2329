"""The language model as a problem: every member of a low-rank population
scored on next-token prediction (``model.py``: the member model;
``problem.py``: the problem and its batches)."""

from .model import DEFAULT_BLOCKS, LMConfig, forward, init_params, param_shapes
from .problem import TokenLMProblem, TokenLMState, packed_row

__all__ = [
    "DEFAULT_BLOCKS",
    "LMConfig",
    "TokenLMProblem",
    "TokenLMState",
    "forward",
    "init_params",
    "packed_row",
    "param_shapes",
]
