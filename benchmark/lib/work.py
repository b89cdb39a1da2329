"""Work counts: the operations and bytes that a cell's semantics need.

Every function here reads the configuration's shapes alone and never looks at
the program, so a roofline share counts the same work whatever implements it.
"""

from __future__ import annotations


def mlp_macs(sizes) -> int:
    """Multiply-accumulates of one forward pass of an MLP with these widths."""
    return sum(int(a) * int(b) for a, b in zip(sizes[:-1], sizes[1:]))


def mlp_dim(sizes) -> int:
    """Genome length of that MLP: weights and biases."""
    return mlp_macs(sizes) + sum(int(b) for b in sizes[1:])


def rollout_flops_per_eval(config: dict) -> int:
    """One evaluation of a policy-rollout configuration, counted at the full
    horizon for every member: ``episodes * T * (2 * policy MACs + physics)``.

    The physics count is the configuration's own (``physics_flops_per_step``:
    for the chain walker 25 masses * 5 substeps * 60 flops). A member that
    falls stops early, so the count overstates the work done by what early
    exits save (about 3 % by docs/PERF_NOTES.md section 9)."""
    per_step = 2 * mlp_macs(config["policy_sizes"]) + int(config["physics_flops_per_step"])
    return int(config["episodes"]) * int(config["episode_len"]) * per_step


def rollout_bytes_per_eval(config: dict) -> int:
    """HBM bytes one evaluation needs: the genome read once, as f32. The
    4 bytes of fitness written are left out (0.005 % of the genome)."""
    return 4 * mlp_dim(config["policy_sizes"])


def generation_hbm_bytes(config: dict, pop: int) -> int:
    """Bytes that one generation of a GA over a ``(pop, d)`` f32 population
    forces through HBM whatever implements it: parents read, offspring
    written, offspring read by evaluate, survivors written. Objectives are
    small beside it and left out."""
    return 4 * int(pop) * int(config["d"]) * 4
