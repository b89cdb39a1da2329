"""Low-rank OpenES over a token language model whose layers are gated short
convolutions and grouped-query attention (``lfm2_moe``): ``lowrank_es_lm``'s
workflow as it stands, with a snapshot that also reads what the convolution
layers count (``conv_gain``, which the comparison holds against the
reference's) and ``attn_blocks``."""

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
        "conv_gain": np.asarray(state.prob.conv_gain),
        "attn_blocks": np.asarray(state.prob.attn_blocks),
    }
