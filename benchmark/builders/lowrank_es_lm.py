"""Low-rank OpenES over the token language model, through ``StdWorkflow``
(the path ``examples/lowrank_es_lm.py`` builds): z-scored fitness, minimise.

Reads from the configuration the model's shapes (``LMConfig.from_dict``),
``rank``, ``noise_stdev``, ``learning_rate``, ``compute_dtype``,
``center_dtype``, ``probe_positions`` and ``blocks``.
From the traffic mix: ``pop``, ``seq_len``, ``rows_per_member``, the
``doc_len_*`` keys, ``ids`` and ``mesh_devices``.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmark.lib.build import Built, key_from_seed


def build(config: dict, traffic: dict, seed: int, devices: list) -> Built:
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.so.es import LowRankOpenES
    from evox_tpu.problems.lm import LMConfig, TokenLMProblem, init_params
    from evox_tpu.utils import standardise

    if int(traffic.get("mesh_devices", 0)) > 1:
        raise ValueError("lowrank_es_lm runs on one device: the spec has no population axis to shard")
    if traffic["ids"] != "uniform":
        raise ValueError(f"lowrank_es_lm knows uniform ids, not {traffic['ids']!r}")
    cfg = LMConfig.from_dict(config)
    key = key_from_seed(seed)
    pop = int(traffic["pop"])
    algo = LowRankOpenES(
        lambda: init_params(cfg, jax.random.fold_in(key, 1)),  # made in init: no second copy
        pop,
        learning_rate=float(config["learning_rate"]),
        noise_stdev=float(config["noise_stdev"]),
        rank=int(config["rank"]),
        compute_dtype=config["compute_dtype"],
        center_dtype=config["center_dtype"],
    )
    problem = TokenLMProblem(
        cfg,
        pop,
        int(traffic["seq_len"]),
        doc_len_median=float(traffic["doc_len_median"]),
        doc_len_sigma=float(traffic["doc_len_sigma"]),
        doc_len_min=int(traffic["doc_len_min"]),
        rows_per_member=int(traffic["rows_per_member"]),
        probe_positions=int(config["probe_positions"]),
        blocks=config.get("blocks"),
    )
    wf = StdWorkflow(algo, problem, opt_direction="min", fit_transforms=(standardise,))
    return Built(wf=wf, key=key, pop=pop, snapshot=snapshot)


def snapshot(state) -> dict:
    """What the comparison reads of a state, on the host."""
    return {
        "generation": int(state.generation),
        "center": [np.asarray(leaf) for leaf in jax.tree.leaves(state.algo.center)],
        "fitness": np.asarray(state.algo.fitness),
        "losses": np.asarray(state.prob.losses),
        "probe": np.asarray(state.prob.probe),
        "held": np.asarray(state.prob.held),
        "imbalance": np.asarray(state.prob.imbalance),
    }
