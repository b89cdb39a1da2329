"""``TokenLMProblem``: the fitness of a member is its mean next-token
negative log-likelihood on the generation's batch, which every member reads
and which is made on the device from the problem's key."""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.distributed import POP_AXIS
from ...core.instrument import LM_FORWARD, scope
from ...core.problem import Problem
from ...core.struct import PyTreeNode, field
from .model import DEFAULT_BLOCKS, LMConfig, forward


def packed_row(key: jax.Array, seq_len: int, vocab: int, median: float, sigma: float,
               min_len: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One row of ``seq_len`` tokens: documents of log-normal length (median
    ``median``, ``sigma`` of the logarithm, clipped to ``min_len`` ..
    ``seq_len``) packed until the row is full, the last cut at its end; ids
    uniform over ``vocab``. Returns ``(ids, doc, pos)``: each token's id, its
    document and its position in it."""
    k_len, k_ids = jax.random.split(key)
    z = jax.random.normal(k_len, (-(-seq_len // min_len),))
    lens = jnp.clip(jnp.round(jnp.exp(math.log(median) + sigma * z)), min_len, seq_len)
    ends = jnp.cumsum(lens.astype(jnp.int32))
    at = jnp.arange(seq_len, dtype=jnp.int32)
    doc = jnp.sum(at[:, None] >= ends[None, :], axis=1).astype(jnp.int32)
    pos = at - jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])[doc]
    ids = jax.random.randint(k_ids, (seq_len,), 0, vocab, dtype=jnp.int32)
    return ids, doc, pos


class TokenLMState(PyTreeNode):
    """``generation``: batches made so far (the next is
    ``fold_in(key, generation)``). Of the last evaluation: every member's
    ``losses``; ``probe``, the float32 logits of members 0 and ``pop / 2``
    (the two signs of pair 0) at the row's last positions; for each expert
    layer ``held``, the routed assignments that landed on held experts,
    ``moved``, the rows the experts' loop gathered, computed and put back
    (``held`` and what fills each expert's last block of a chunk), and
    ``imbalance``, the largest held expert's load over the mean;
    ``attn_blocks``, the key blocks the attention kernel's loop bounds visited
    over the key blocks of a dense causal pass (how much of the row's
    attention the documents let it skip; 1 where the plain body ran); for
    each KDA layer ``kda_retention``, the mean of ``exp(g)`` over members,
    tokens, heads and channels (how much of the state a token keeps), and
    ``kda_boundary_chunks``, the scan's chunks in which a document starts
    over its chunks (how often the reset inside a chunk runs); for each gated
    convolution layer ``conv_gain``, the root mean square of the mixer's
    output over that of its normed input, over members, tokens and channels,
    and the same over the documents' first ``taps - 1`` tokens alone, where the
    taps' document mask acts (two numbers a layer)."""

    key: jax.Array = field(sharding=P())
    generation: jax.Array = field(sharding=P())
    losses: jax.Array = field(sharding=P(POP_AXIS), storage=False)
    probe: jax.Array = field(sharding=P())
    held: jax.Array = field(sharding=P())
    moved: jax.Array = field(sharding=P())
    imbalance: jax.Array = field(sharding=P())
    attn_blocks: jax.Array = field(sharding=P())
    kda_retention: jax.Array = field(sharding=P())
    kda_boundary_chunks: jax.Array = field(sharding=P())
    conv_gain: jax.Array = field(sharding=P())


class TokenLMProblem(Problem):
    """Candidates: a :class:`~evox_tpu.core.lowrank.LowRankPopulation` whose
    centre is ``init_params(cfg, key)``'s tree (``LowRankOpenES.ask`` gives
    one). A member is never materialised: the perturbation is applied inside
    the forward pass (``model.py``).

    ``pop_size``: the population's (the state keeps every member's loss).
    ``rows_per_member``: only 1 (one packed row a generation, read by every
    member). ``blocks``: how the forward pass is cut so that it fits
    (``model.DEFAULT_BLOCKS``)."""

    def __init__(
        self,
        cfg: LMConfig,
        pop_size: int,
        seq_len: int,
        doc_len_median: float = 384.0,
        doc_len_sigma: float = 1.0,
        doc_len_min: int = 16,
        rows_per_member: int = 1,
        probe_positions: int = 64,
        blocks: Optional[dict] = None,
    ):
        if rows_per_member != 1:
            raise ValueError("TokenLMProblem scores one packed row a member (rows_per_member=1)")
        if not 1 <= doc_len_min <= seq_len:
            raise ValueError(f"doc_len_min {doc_len_min} is outside 1 .. seq_len {seq_len}")
        self.cfg = cfg
        self.pop_size = int(pop_size)
        self.seq_len = int(seq_len)
        self.doc_len = (float(doc_len_median), float(doc_len_sigma), int(doc_len_min))
        self.n_probe = min(int(probe_positions), self.seq_len)
        self.blocks = {**DEFAULT_BLOCKS, **(blocks or {})}

    def batch(self, key: jax.Array, generation: Any) -> Tuple[jax.Array, jax.Array, jax.Array]:
        return packed_row(
            jax.random.fold_in(key, generation), self.seq_len, self.cfg.vocab_size, *self.doc_len
        )

    def init(self, key: Optional[jax.Array] = None) -> TokenLMState:
        n = self.cfg.expert_layers
        return TokenLMState(
            key=jax.random.PRNGKey(0) if key is None else key,
            generation=jnp.zeros((), jnp.int32),
            losses=jnp.zeros((self.pop_size,), jnp.float32),
            probe=jnp.zeros((2, self.n_probe, self.cfg.vocab_size), jnp.float32),
            held=jnp.zeros((n,), jnp.int32),
            moved=jnp.zeros((n,), jnp.int32),
            imbalance=jnp.zeros((n,), jnp.float32),
            attn_blocks=jnp.zeros((), jnp.float32),
            kda_retention=jnp.zeros((self.cfg.kda_layers,), jnp.float32),
            kda_boundary_chunks=jnp.zeros((self.cfg.kda_layers,), jnp.float32),
            conv_gain=jnp.zeros((self.cfg.conv_layers, 2), jnp.float32),
        )

    def evaluate(self, state: TokenLMState, pop: Any) -> Tuple[jax.Array, TokenLMState]:
        if not hasattr(pop, "factors"):
            raise TypeError(
                "TokenLMProblem evaluates a LowRankPopulation (LowRankOpenES.ask), "
                f"not {type(pop).__name__}: a member is too large to be a row of a population"
            )
        with scope(LM_FORWARD):
            ids, doc, pos = self.batch(state.key, state.generation)
        out = forward(self.cfg, pop.center, pop.factors, pop.scale, ids, doc, pos,
                      self.n_probe, self.blocks)
        losses = out["losses"].T.reshape(-1)  # the + half, then the - half
        return losses, state.replace(
            generation=state.generation + 1, losses=losses, probe=out["probe"],
            held=out["held"], moved=out["moved"], imbalance=out["imbalance"],
            attn_blocks=out["attn_blocks"], kda_retention=out["kda_retention"],
            kda_boundary_chunks=out["kda_boundary_chunks"], conv_gain=out["conv_gain"],
        )
