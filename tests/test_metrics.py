"""Metric golden tests, mirroring reference tests/test_metrics.py
(closed-form GD/IGD values; Monte-Carlo HV vs analytic)."""

import jax
import jax.numpy as jnp
import numpy as np

from evox_tpu.metrics import gd, gd_plus, hypervolume_mc, igd, igd_plus


PF = jnp.asarray([[0.0, 1.0], [0.5, 0.5], [1.0, 0.0]])
OBJS = jnp.asarray([[0.0, 1.5], [1.0, 0.5]])


def test_gd_closed_form():
    # nearest distances: [0,1.5]->[0,1]=0.5 ; [1,0.5]->[0.5,0.5] or [1,0]=0.5
    np.testing.assert_allclose(float(gd(OBJS, PF)), 0.5, rtol=1e-5)


def test_igd_closed_form():
    # per-PF-point nearest solution distances:
    # [0,1]->0.5 ; [0.5,0.5]->0.5 ; [1,0]->0.5
    np.testing.assert_allclose(float(igd(OBJS, PF)), 0.5, rtol=1e-5)


def test_gd_plus_dominated_only():
    objs = jnp.asarray([[0.0, 0.5]])  # dominates PF point [0,1]
    assert float(gd_plus(objs, PF)) == 0.0


def test_igd_plus_leq_igd():
    assert float(igd_plus(OBJS, PF)) <= float(igd(OBJS, PF)) + 1e-6


def test_hypervolume_mc_vs_analytic():
    # single point [0.5, 0.5] with ref [1, 1]: HV = 0.25
    objs = jnp.asarray([[0.5, 0.5]])
    hv = hypervolume_mc(jax.random.PRNGKey(0), objs, jnp.asarray([1.0, 1.0]))
    np.testing.assert_allclose(float(hv), 0.25, atol=0.01)


def test_hypervolume_each_cube():
    objs = jnp.asarray([[0.25, 0.75], [0.75, 0.25]])
    # exact: 2 * 0.75*0.25 - overlap 0.25*0.25 = 0.3125
    hv = hypervolume_mc(
        jax.random.PRNGKey(1), objs, jnp.asarray([1.0, 1.0]),
        sample_method="each_cube",
    )
    np.testing.assert_allclose(float(hv), 0.3125, atol=0.01)


def test_hypervolume_2d_exact():
    """Exact 2-D HV on a hand-computable staircase, vs brute rectangles,
    dominated/outside points ignored, and MC agreement."""
    ref = jnp.array([4.0, 4.0])
    objs = jnp.array(
        [
            [1.0, 3.0],
            [2.0, 2.0],
            [3.0, 1.0],
            [2.5, 2.5],  # dominated by (2, 2)
            [5.0, 0.5],  # outside ref on f1
        ]
    )
    # staircase area: x in [1,2): h=1; [2,3): h=2; [3,4): h=3 -> 1+2+3 = 6
    from evox_tpu.metrics import hypervolume_2d, hypervolume_mc

    hv = float(hypervolume_2d(objs, ref))
    assert abs(hv - 6.0) < 1e-6, hv
    # permutation invariance
    perm = jax.random.permutation(jax.random.PRNGKey(0), objs.shape[0])
    assert abs(float(hypervolume_2d(objs[perm], ref)) - 6.0) < 1e-6
    # MC agrees within sampling error on a random front
    key = jax.random.PRNGKey(1)
    pts = jax.random.uniform(key, (64, 2)) * 3.0
    exact = float(hypervolume_2d(pts, ref))
    mc = float(hypervolume_mc(jax.random.PRNGKey(2), pts, ref, num_samples=200_000))
    assert abs(exact - mc) / exact < 0.02, (exact, mc)


def test_hv_class_dispatches_exact_for_2d():
    from evox_tpu.metrics import HV, hypervolume_2d

    pts = jax.random.uniform(jax.random.PRNGKey(3), (32, 2)) * 3.0
    ref = jnp.array([4.0, 4.0])
    hv = HV(ref=ref)
    # exact path: result is deterministic and equals hypervolume_2d
    a = float(hv(jax.random.PRNGKey(0), pts))
    b = float(hv(jax.random.PRNGKey(99), pts))
    assert a == b == float(hypervolume_2d(pts, ref))


def test_hypervolume_3d_golden_values():
    """Exact 3-D HV against analytic cases."""
    from evox_tpu.metrics import hypervolume_3d

    ref = jnp.array([1.0, 1.0, 1.0])
    # one point: box volume
    one = jnp.array([[0.5, 0.25, 0.5]])
    np.testing.assert_allclose(
        float(hypervolume_3d(one, ref)), 0.5 * 0.75 * 0.5, rtol=1e-6
    )
    # dominated point adds nothing
    two = jnp.array([[0.5, 0.25, 0.5], [0.75, 0.5, 0.75]])
    np.testing.assert_allclose(
        float(hypervolume_3d(two, ref)), 0.5 * 0.75 * 0.5, rtol=1e-6
    )
    # two disjoint boxes: volumes add (no overlap in f1)
    disj = jnp.array([[0.0, 0.8, 0.8], [0.8, 0.0, 0.0]])
    expected = (1.0 * 0.2 * 0.2) + (0.2 * 1.0 * 1.0) - 0.2 * 0.2 * 0.2
    np.testing.assert_allclose(float(hypervolume_3d(disj, ref)), expected, rtol=1e-6)
    # point outside the box contributes nothing
    out = jnp.array([[0.5, 0.5, 0.5], [2.0, 2.0, 2.0]])
    np.testing.assert_allclose(float(hypervolume_3d(out, ref)), 0.125, rtol=1e-6)
    # inclusion-exclusion on two overlapping boxes
    ovl = jnp.array([[0.2, 0.4, 0.4], [0.4, 0.2, 0.2]])
    va = 0.8 * 0.6 * 0.6
    vb = 0.6 * 0.8 * 0.8
    vab = 0.6 * 0.6 * 0.6
    np.testing.assert_allclose(float(hypervolume_3d(ovl, ref)), va + vb - vab, rtol=1e-6)


def test_hypervolume_3d_matches_mc_on_random_front():
    from evox_tpu.metrics import hypervolume_3d, hypervolume_mc

    key = jax.random.PRNGKey(0)
    # random points on the simplex-ish front plus noise
    pts = jax.random.uniform(jax.random.PRNGKey(1), (32, 3)) * 0.8
    ref = jnp.ones((3,))
    exact = float(hypervolume_3d(pts, ref))
    est = float(hypervolume_mc(key, pts, ref, num_samples=200_000))
    assert abs(est - exact) / exact < 0.05, (exact, est)


def test_hypervolume_contributions_exact():
    from evox_tpu.metrics import (
        hypervolume_2d,
        hypervolume_3d,
        hypervolume_contributions,
    )

    ref = jnp.ones((3,))
    pts = jnp.array(
        [[0.2, 0.6, 0.5], [0.6, 0.2, 0.4], [0.5, 0.5, 0.2], [0.7, 0.7, 0.7]]
    )
    contrib = np.asarray(hypervolume_contributions(pts, ref))
    # brute-force leave-one-out
    total = float(hypervolume_3d(pts, ref))
    for i in range(4):
        rest = jnp.asarray(np.delete(np.asarray(pts), i, axis=0))
        expected = total - float(hypervolume_3d(rest, ref))
        np.testing.assert_allclose(contrib[i], expected, rtol=1e-5, atol=1e-7)
    # m=2 path too
    ref2 = jnp.ones((2,))
    pts2 = jnp.array([[0.2, 0.6], [0.6, 0.2], [0.9, 0.9]])
    c2 = np.asarray(hypervolume_contributions(pts2, ref2))
    t2 = float(hypervolume_2d(pts2, ref2))
    for i in range(3):
        rest = jnp.asarray(np.delete(np.asarray(pts2), i, axis=0))
        np.testing.assert_allclose(
            c2[i], max(t2 - float(hypervolume_2d(rest, ref2)), 0.0),
            rtol=1e-6, atol=1e-7,
        )
    assert c2[2] == 0.0  # dominated point: zero exclusive contribution


def test_hv_class_dispatches_exact_for_3d():
    from evox_tpu.metrics import HV, hypervolume_3d

    pts = jax.random.uniform(jax.random.PRNGKey(3), (16, 3))
    ref = jnp.full((3,), 1.5)
    hv = HV(ref=ref)
    a = float(hv(jax.random.PRNGKey(0), pts))
    b = float(hv(jax.random.PRNGKey(1), pts))
    assert a == b  # exact: key-independent
    np.testing.assert_allclose(a, float(hypervolume_3d(pts, ref)), rtol=1e-7)


def test_hype_exact_contrib_3d_per_front():
    """HypE's m=3 exact per-front contributions agree with brute-force
    front-restricted leave-one-out, and the m=3 dispatch uses them."""
    from evox_tpu.algorithms.mo.hype import HypE, exact_contrib_3d
    from evox_tpu.metrics import hypervolume_3d
    from evox_tpu.operators.selection.non_dominate import non_dominated_sort

    fit = jnp.array(
        [[0.2, 0.6, 0.5], [0.6, 0.2, 0.4], [0.5, 0.5, 0.2],  # front 0
         [0.7, 0.7, 0.7], [0.9, 0.3, 0.6]]
    )
    ref = jnp.full((3,), 1.2)
    rank = non_dominated_sort(fit)
    contrib = np.asarray(exact_contrib_3d(fit, ref, rank))
    n = fit.shape[0]
    idx = np.arange(n)
    for i in range(n):
        front = np.asarray(rank) == int(rank[i])
        with_i = float(hypervolume_3d(fit, ref, mask=jnp.asarray(front)))
        without = float(
            hypervolume_3d(fit, ref, mask=jnp.asarray(front & (idx != i)))
        )
        np.testing.assert_allclose(
            contrib[i], max(with_i - without, 0.0), rtol=1e-6, atol=1e-8
        )

    algo = HypE(jnp.zeros(4), jnp.ones(4), n_objs=3, pop_size=8)
    score = algo._score(jax.random.PRNGKey(0), fit, ref, rank, 2)
    np.testing.assert_allclose(np.asarray(score), contrib, rtol=1e-6)
    # above the exact cutoff it falls back to MC (finite, non-negative)
    algo_mc = HypE(jnp.zeros(4), jnp.ones(4), n_objs=3, pop_size=8, exact_hv_max_n=0)
    s_mc = algo_mc._score(jax.random.PRNGKey(0), fit, ref, rank, 2)
    assert np.isfinite(np.asarray(s_mc)).all()
