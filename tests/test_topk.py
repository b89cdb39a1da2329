"""Pallas partial-top-k kernel (kernels/topk.py) — interpret-mode parity
on the CPU CI mesh (per CLAUDE.md, interpret-mode passing is NOT
real-chip compile evidence; the compile for a described v5e is a case
of tests/test_chip_compile.py) plus the wired selection sites:
truncation selection, pbest sampling, island migration elites, and the
NSGA-II last-front truncation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu.kernels.topk import (
    default_use_kernel,
    partial_topk,
    partial_topk_reference,
)
from evox_tpu.operators.selection.basic import select_rand_pbest, topk_fit
from evox_tpu.operators.selection.non_dominate import rank_crowding_truncate


@pytest.mark.parametrize(
    "n,k,bs",
    [
        (3000, 7, 256),
        (2500, 128, 256),
        (4096, 256, 1024),
        (1500, 1, 128),
        (300, 50, 128),
        (1025, 64, 128),  # ragged final tile
    ],
)
def test_kernel_matches_lax_topk_exactly(n, k, bs):
    """Values AND indices identical to lax.top_k on the negated input —
    including duplicates and ±inf sentinels (the masked-min extraction
    exists precisely because a one-hot matmul would NaN on inf*0)."""
    v = jax.random.uniform(jax.random.PRNGKey(n), (n,))
    v = (
        v.at[5].set(v[0])
        .at[7].set(v[0])
        .at[n // 2].set(jnp.inf)
        .at[n // 3].set(jnp.inf)
        .at[11].set(-jnp.inf)
        .at[n - 2].set(-jnp.inf)
    )
    rv, ri = partial_topk_reference(v, k)
    kv, ki = partial_topk(v, k, use_kernel=True, interpret=True, block_size=bs)
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(kv))
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(ki))


@pytest.mark.slow
def test_kernel_tie_law_on_duplicate_heavy_input():
    """Quantized values force cross-block value ties: the block-major,
    rank-ordered candidate layout must preserve lax.top_k's
    lowest-index tie law through the merge."""
    v = jnp.round(jax.random.uniform(jax.random.PRNGKey(0), (5000,)) * 10) / 10
    rv, ri = partial_topk_reference(v, 64)
    kv, ki = partial_topk(v, 64, use_kernel=True, interpret=True, block_size=256)
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(kv))
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(ki))


def test_kernel_vmaps_over_batches():
    """The island-migration shape: per-island top-k under jax.vmap."""
    f = jax.random.uniform(jax.random.PRNGKey(1), (4, 2000))
    idx = jax.vmap(
        lambda v: partial_topk(v, 3, use_kernel=True, interpret=True, block_size=256)[1]
    )(f)
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(jnp.argsort(f, axis=1)[:, :3])
    )


def test_default_off_and_fallback_envelope():
    """use_kernel=None resolves off everywhere until the real-TPU compile
    check is recorded; out-of-envelope calls (k > block, tiny n) fall
    back silently with identical results."""
    assert default_use_kernel() is False
    v = jax.random.uniform(jax.random.PRNGKey(2), (300,))
    rv, ri = partial_topk_reference(v, 200)
    # k > block_size: falls back even with use_kernel=True
    kv, ki = partial_topk(v, 200, use_kernel=True, interpret=True, block_size=128)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(ki))
    with pytest.raises(ValueError, match="k must be"):
        partial_topk(v, 0)
    with pytest.raises(ValueError, match="block_size"):
        partial_topk(v, 5, use_kernel=True, interpret=True, block_size=100)
    with pytest.raises(ValueError, match="1-D"):
        partial_topk(v.reshape(30, 10), 5)


def test_topk_fit_kernel_path_identical():
    """topk_fit through the kernel: same survivors, same fitness, same
    order as the lax.top_k path (the operator's bit-compat contract)."""
    key = jax.random.PRNGKey(3)
    pop = jax.random.normal(key, (2000, 6))
    fit = jax.random.uniform(jax.random.fold_in(key, 1), (2000,))
    p_ref, f_ref = topk_fit(pop, fit, 32)
    p_ker, f_ker = topk_fit(pop, fit, 32, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_ker))
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_ker))


def test_select_rand_pbest_kernel_path_identical():
    key = jax.random.PRNGKey(4)
    pop = jax.random.normal(key, (2000, 4))
    fit = jax.random.uniform(jax.random.fold_in(key, 1), (2000,))
    sel_key = jax.random.fold_in(key, 2)
    a = select_rand_pbest(sel_key, 0.1, pop, fit)
    b = select_rand_pbest(sel_key, 0.1, pop, fit, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------- NSGA-II last-front truncation


def _truncation_sets_agree(fit, k):
    o_ref, r_ref = rank_crowding_truncate(fit, k)
    o_ker, r_ker = rank_crowding_truncate(fit, k, use_kernel=True, interpret=True)
    o_ref, o_ker = np.asarray(o_ref), np.asarray(o_ker)
    assert set(o_ref.tolist()) == set(o_ker.tolist()), "survivor sets differ"
    assert len(set(o_ker.tolist())) == k, "kernel path duplicated a survivor"
    ranks = {int(i): int(r) for i, r in zip(o_ref, np.asarray(r_ref))}
    assert all(
        ranks[int(i)] == int(r) for i, r in zip(o_ker, np.asarray(r_ker))
    ), "per-survivor ranks differ"


@pytest.mark.slow
def test_rank_crowding_truncate_kernel_set_identical():
    """The kernel path admits EXACTLY the lexsort path's survivor set
    (whole better fronts + crowding-selected cut front, ties by lowest
    index); only the returned order differs (documented law)."""
    fit = jax.random.uniform(jax.random.PRNGKey(5), (3000, 3))
    _truncation_sets_agree(fit, 1000)
    # many tiny fronts (1-D-ish fitness): deep peel, small cut front
    fit2 = jnp.stack(
        [jnp.linspace(0, 1, 600), jnp.linspace(0, 1, 600) ** 2], axis=1
    )
    _truncation_sets_agree(fit2, 100)
    # single front: truncation is pure crowding selection
    fit3 = jnp.stack(
        [jnp.linspace(0, 1, 500), jnp.linspace(1, 0, 500)], axis=1
    )
    _truncation_sets_agree(fit3, 100)


def test_nsga2_kernel_mode_converges_zdt1():
    """Convergence-threshold gate (CLAUDE.md) for the selection-law-
    equivalent kernel truncation: NSGA-II with use_kernel on reaches the
    suite's ZDT1 IGD bar at 150 generations (tests/test_mo_algorithms.py).

    At generation 100 the search is still on the steep part of its
    descent, where the IGD swings with the seed on EITHER truncation
    path (0.03 to 0.11 over three seeds, the lexsort path 0.092 where
    this one read 0.108 on seed 3), so a bar of 0.1 there tested the
    build's PRNG. By generation 150 both paths read 0.008 to 0.015."""
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.mo import NSGA2
    from evox_tpu.metrics import igd
    from evox_tpu.problems.numerical import ZDT1

    d = 12
    algo = NSGA2(
        jnp.zeros(d),
        jnp.ones(d),
        n_objs=2,
        pop_size=100,
        use_kernel=True,
        topk_interpret=True,  # the kernel body on the CPU CI backend
    )
    wf = StdWorkflow(algo, ZDT1(n_dim=d))
    state = wf.init(jax.random.PRNGKey(3))
    state = wf.run(state, 150)
    fit = state.algo.fitness
    finite = jnp.isfinite(fit).all(axis=1)
    fit = jnp.where(finite[:, None], fit, 1e6)
    assert float(igd(fit, ZDT1(n_dim=d).pf())) < 0.05


def test_islands_topk_kernel_migration_matches_argsort():
    """IslandWorkflow elites through the kernel: identical migration
    (same elite indices as the stable argsort) — asserted by running two
    otherwise-identical island workflows to bitwise-equal states."""
    from evox_tpu import IslandWorkflow
    from evox_tpu.algorithms.so.pso import PSO
    from evox_tpu.problems.numerical import Sphere

    def mk(**kw):
        return IslandWorkflow(
            PSO(lb=-jnp.ones(4), ub=jnp.ones(4), pop_size=8),
            Sphere(),
            n_islands=4,
            migrate_every=2,
            migrate_k=2,
            **kw,
        )

    key = jax.random.PRNGKey(6)
    wf_a = mk()
    s_a = wf_a.run(wf_a.init(key), 6)
    wf_b = mk(use_topk_kernel=True, topk_interpret=True)
    s_b = wf_b.run(wf_b.init(key), 6)
    for leaf_a, leaf_b in zip(jax.tree.leaves(s_a.algo), jax.tree.leaves(s_b.algo)):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))
