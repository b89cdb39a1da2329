"""Low-rank OpenES over a token language model whose layers are of two kinds
(KDA and MLA): ``lowrank_es_lm``'s workflow as it stands, with a snapshot that
also reads what the KDA layers count (``kda_retention``, which the comparison
holds against the reference's, and ``kda_boundary_chunks``)."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.builders import lowrank_es_lm
from benchmark.lib.build import Built


def build(config: dict, traffic: dict, seed: int, devices: list) -> Built:
    return dataclasses.replace(lowrank_es_lm.build(config, traffic, seed, devices), snapshot=snapshot)


def snapshot(state) -> dict:
    """What the comparison reads of a state, on the host."""
    return {
        **lowrank_es_lm.snapshot(state),
        "kda_retention": np.asarray(state.prob.kda_retention),
        "kda_boundary_chunks": np.asarray(state.prob.kda_boundary_chunks),
    }
