"""Big-policy fused rollout kernel (kernels/rollout_mlp.py): plane math
pinned exactly against an out-of-Pallas reference loop, and the full
engine pinned against the standard scan/while engine on the walker."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu.kernels.rollout_mlp import (
    _mlp_planes,
    chain_walker_planes,
    fused_mlp_rollout,
)
from evox_tpu.problems.neuroevolution import PolicyRolloutProblem, mlp_policy
from evox_tpu.utils import TreeAndVector

SIZES = (244, 16, 8, 17)  # small hiddens: CI-speed, same code paths


def _make_params(key, n, sizes=SIZES):
    ks = jax.random.split(key, 2 * (len(sizes) - 1))
    weights, biases = [], []
    for i in range(len(sizes) - 1):
        w = 0.2 * jax.random.normal(ks[2 * i], (sizes[i], sizes[i + 1], n))
        b = 0.1 * jax.random.normal(ks[2 * i + 1], (sizes[i + 1], n))
        weights.append(w)
        biases.append(b)
    return tuple(weights), tuple(biases)


def _loop_reference(weights, biases, planes0, T, penv, sizes):
    """The kernel's own math on full (C, n) planes outside Pallas."""
    state = {k: v for k, v in planes0.items()}
    done = state.pop("done") > 0.5
    total = jnp.zeros_like(done, dtype=jnp.float32)
    for _ in range(T):
        obs = penv.obs_planes(state)
        act = _mlp_planes(weights, biases, obs, sizes)
        state, reward, step_done = penv.step_planes(state, act)
        total = total + jnp.where(done, 0.0, reward)
        done = done | step_done
    return total.reshape(-1)


def _walker_setup(n, ep=1, max_steps=12, seed=0):
    penv = chain_walker_planes(max_steps=max_steps)
    keys = jax.random.split(jax.random.PRNGKey(seed), ep)
    env0 = jax.vmap(penv.base.reset)(keys)
    env_flat = jax.tree.map(
        lambda x: jnp.broadcast_to(x[:, None], (ep, n) + x.shape[1:]).reshape(
            (ep * n,) + x.shape[1:]
        ),
        env0,
    )
    return penv, penv.to_planes(env_flat)


@pytest.mark.parametrize(
    "early_stop",
    [pytest.param(True, marks=pytest.mark.slow), False],
    ids=["while", "fori"],
)
# n=150 is the stress shape; the n=5 variants carry the exactness law in
# tier-1 (ISSUE 14 gate-headroom: the PR-2 slow-marking discipline)
@pytest.mark.parametrize(
    "n", [5, pytest.param(150, marks=pytest.mark.slow)]
)
def test_fused_mlp_exact_vs_plane_loop(n, early_stop):
    """Tiling, padding, both loop forms and the weight layout reproduce
    the plane math exactly (n=5 exercises padding, 150 one full tile
    PLUS a ragged final tile — the exact-tile n=128 case is a strict
    subset of its first tile; early_stop covers the packed-carry
    while_loop AND the fori fallback for never-terminating envs)."""
    penv, planes0 = _walker_setup(n, max_steps=6)
    weights, biases = _make_params(jax.random.PRNGKey(1), n)
    got = fused_mlp_rollout(
        weights, biases, planes0, T=6, sizes=SIZES,
        step_planes=penv.step_planes, obs_planes=penv.obs_planes,
        early_stop=early_stop, interpret=True,
    )
    want = _loop_reference(weights, biases, planes0, 6, penv, SIZES)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.slow
def test_fused_mlp_episode_major_grid():
    n, ep = 12, 2
    penv, planes0 = _walker_setup(n, ep=ep, max_steps=3)
    weights, biases = _make_params(jax.random.PRNGKey(2), n)
    got = fused_mlp_rollout(
        weights, biases, planes0, T=3, sizes=SIZES,
        step_planes=penv.step_planes, obs_planes=penv.obs_planes,
        episodes=ep, interpret=True,
    )
    # reference: tile weights episode-major and run the plane loop
    w_rep = tuple(jnp.tile(w, (1, 1, ep)) for w in weights)
    b_rep = tuple(jnp.tile(b, (1, ep)) for b in biases)
    want = _loop_reference(w_rep, b_rep, planes0, 3, penv, SIZES)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_planes_walker_matches_aos_walker():
    """chain_walker_planes is the SAME physics as control/walker.py: one
    step from identical states produces identical rewards/done and the
    observation vector row order matches exactly."""
    from evox_tpu.problems.neuroevolution.control import chain_walker

    env = chain_walker(max_steps=50)
    penv = chain_walker_planes(max_steps=50)
    n = 7
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    states = jax.vmap(env.reset)(keys)
    planes = penv.to_planes(states)

    # observation parity
    obs_aos = jax.vmap(env.obs)(states)  # (n, 244)
    obs_pl = penv.obs_planes({k: v for k, v in planes.items() if k != "done"})
    np.testing.assert_allclose(
        np.asarray(obs_pl.T), np.asarray(obs_aos), rtol=2e-5, atol=2e-5
    )

    # step parity (a few steps with a fixed action pattern)
    act = 0.3 * jnp.sin(jnp.arange(17.0))
    aos_state, pl_state = states, {k: v for k, v in planes.items() if k != "done"}
    for _ in range(5):
        aos_state, r_aos, d_aos = jax.vmap(env.step, in_axes=(0, None))(
            aos_state, act
        )
        pl_state, r_pl, d_pl = penv.step_planes(
            pl_state, jnp.broadcast_to(act[:, None], (17, n))
        )
        np.testing.assert_allclose(
            np.asarray(r_pl[0]), np.asarray(r_aos), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_array_equal(np.asarray(d_pl[0]), np.asarray(d_aos))


@pytest.mark.slow
def test_fused_planes_engine_matches_scan_engine():
    """PolicyRolloutProblem(fused_planes=...) reproduces the standard
    early-exit engine's fitness on the walker with mlp_policy params."""
    penv = chain_walker_planes(max_steps=25)
    init_params, apply = mlp_policy((244, 16, 8, 17))
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    pop_flat = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (6, adapter.dim))
    pop_tree = jax.vmap(adapter.to_tree)(pop_flat)

    kw = dict(num_episodes=2, stochastic_reset=False)
    scan_prob = PolicyRolloutProblem(apply, penv.base, **kw)
    fused_prob = PolicyRolloutProblem(
        apply, penv.base, fused_planes=penv, fused_interpret=True, **kw
    )
    s_scan = scan_prob.init(jax.random.PRNGKey(9))
    s_fused = fused_prob.init(jax.random.PRNGKey(9))
    f_scan, _ = scan_prob.evaluate(s_scan, pop_tree)
    f_fused, _ = fused_prob.evaluate(s_fused, pop_tree)
    np.testing.assert_allclose(
        np.asarray(f_fused), np.asarray(f_scan), rtol=2e-3, atol=2e-3
    )


@pytest.mark.slow
def test_fused_planes_multichip_shard_map():
    """The big-policy engine also runs per-shard under the shard_map
    evaluation path on a mesh, matching single-device."""
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.core.distributed import create_mesh
    from evox_tpu.utils import TreeAndVector

    penv = chain_walker_planes(max_steps=10)
    init_params, apply = mlp_policy((244, 16, 8, 17))
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))

    def build(mesh=None, island=False):
        prob = PolicyRolloutProblem(
            apply, penv.base, num_episodes=1, stochastic_reset=False,
            fused_planes=penv, fused_interpret=True,
        )
        algo = OpenES(jnp.zeros(adapter.dim), 16, learning_rate=0.05)
        return StdWorkflow(
            algo, prob, opt_direction="max",
            pop_transforms=(adapter.batched_to_tree,),
            mesh=mesh, eval_shard_map=island,
        )

    mesh = create_mesh()
    centers = []
    for mesh_arg, island in ((mesh, True), (None, False)):
        wf = build(mesh_arg, island)
        st = wf.init(jax.random.PRNGKey(1))
        st = wf.step(st)
        centers.append(np.asarray(st.algo.center))
    np.testing.assert_allclose(centers[0], centers[1], rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_fused_planes_low_rank_linear_matches_scan():
    """A rank-r factorized input layer (linear_layers=(0,)) runs through
    the fused kernel bit-compatibly with the scan engine — the
    fewer-MACs structured policy."""
    penv = chain_walker_planes(max_steps=20)
    init_params, apply = mlp_policy((244, 8, 16, 17), linear_layers=(0,))
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    pop_flat = 0.2 * jax.random.normal(jax.random.PRNGKey(5), (6, adapter.dim))
    pop_tree = jax.vmap(adapter.to_tree)(pop_flat)

    kw = dict(num_episodes=2, stochastic_reset=False)
    scan_prob = PolicyRolloutProblem(apply, penv.base, **kw)
    fused_prob = PolicyRolloutProblem(
        apply, penv.base, fused_planes=penv, fused_interpret=True,
        fused_planes_linear=(0,), **kw
    )
    f_scan, _ = scan_prob.evaluate(scan_prob.init(jax.random.PRNGKey(9)), pop_tree)
    f_fused, _ = fused_prob.evaluate(fused_prob.init(jax.random.PRNGKey(9)), pop_tree)
    np.testing.assert_allclose(
        np.asarray(f_fused), np.asarray(f_scan), rtol=2e-3, atol=2e-3
    )
    # and the probe rejects a mismatched linear spec
    bad = PolicyRolloutProblem(
        apply, penv.base, fused_planes=penv, fused_interpret=True, **kw
    )
    with pytest.raises(ValueError, match="disagrees"):
        bad.evaluate(bad.init(jax.random.PRNGKey(9)), pop_tree)


def test_fused_planes_rejects_wrong_policy():
    penv = chain_walker_planes(max_steps=10)
    init_params, apply = mlp_policy((244, 16, 8, 17), activation=jax.nn.relu)
    params = init_params(jax.random.PRNGKey(0))
    pop_tree = jax.tree.map(lambda x: x[None].repeat(4, axis=0), params)
    prob = PolicyRolloutProblem(
        apply, penv.base, fused_planes=penv, fused_interpret=True
    )
    state = prob.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="disagrees"):
        prob.evaluate(state, pop_tree)


@pytest.mark.slow
def test_fused_mlp_bf16_residency_close_to_f32():
    """weight_dtype=bfloat16 keeps VMEM-resident policy planes in bf16
    (f32 accumulate, f32 env math): totals stay close to the f32 run and
    the output dtype stays f32."""
    n, T = 128, 8
    penv, planes0 = _walker_setup(n, max_steps=T)
    weights, biases = _make_params(jax.random.PRNGKey(2), n)
    kw = dict(
        T=T, sizes=SIZES, step_planes=penv.step_planes,
        obs_planes=penv.obs_planes, tile=128, episodes=1, interpret=True,
    )
    tot_f32 = fused_mlp_rollout(weights, biases, dict(planes0), **kw)
    tot_bf16 = fused_mlp_rollout(
        weights, biases, dict(planes0), weight_dtype=jnp.bfloat16, **kw
    )
    assert tot_bf16.dtype == jnp.float32
    # bf16 weights perturb actions ~0.4% relative; totals track within a
    # loose tolerance (chaotic contact dynamics amplify tiny differences)
    err = np.abs(np.asarray(tot_bf16) - np.asarray(tot_f32))
    scale = np.maximum(np.abs(np.asarray(tot_f32)), 1.0)
    assert np.median(err / scale) < 0.1, (err / scale)


@pytest.mark.slow
def test_bf16_rollouts_train_walker():
    """Convergence with bf16-resident policies: OpenES on a small walker
    still improves the center policy's episode return (reduced
    precision must not break training)."""
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.utils import rank_based_fitness

    penv = chain_walker_planes(
        n_masses=7, act_dim=4, obs_dim=64, max_steps=40
    )
    env = penv.base
    init_params, apply = mlp_policy((env.obs_dim, 16, 16, env.act_dim))
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    prob = PolicyRolloutProblem(
        apply, env, num_episodes=1, stochastic_reset=False,
        fused_planes=penv, fused_interpret=True,
        fused_planes_dtype=jnp.bfloat16,
    )
    center0 = 0.1 * jax.random.normal(jax.random.PRNGKey(123), (adapter.dim,))
    algo = OpenES(center0, pop_size=48, learning_rate=0.05, noise_stdev=0.05)
    wf = StdWorkflow(
        algo, prob, opt_direction="max",
        pop_transforms=(adapter.batched_to_tree,),
        fit_transforms=(rank_based_fitness,),
    )
    state = wf.init(jax.random.PRNGKey(7))

    def center_reward(state):
        pstate = prob.init(jax.random.PRNGKey(99))
        fit, _ = prob.evaluate(
            pstate, jax.vmap(adapter.to_tree)(state.algo.center[None, :])
        )
        return float(fit[0])

    before = center_reward(state)
    state = wf.run(state, 10)
    after = center_reward(state)
    assert after > before, (before, after)


def test_fused_mlp_rejects_out_of_range_linear():
    """Regression: an out-of-range `linear` index used to
    be silently ignored (the user would train a different architecture
    than requested); fused_mlp_rollout now mirrors
    mlp_policy(linear_layers=...)'s range check."""
    n = 5
    penv, planes0 = _walker_setup(n, max_steps=3)
    weights, biases = _make_params(jax.random.PRNGKey(5), n)
    kw = dict(
        T=3, sizes=SIZES, step_planes=penv.step_planes,
        obs_planes=penv.obs_planes, interpret=True,
    )
    n_layers = len(SIZES) - 1
    for bad in ((n_layers,), (-1,), (0, 99)):
        with pytest.raises(ValueError, match="out of range"):
            fused_mlp_rollout(weights, biases, planes0, linear=bad, **kw)
    # in-range indices still work
    got = fused_mlp_rollout(
        weights, biases, planes0, linear=(0,), **kw
    )
    assert got.shape == (n,)


# ------------------------------------------------- the flat genome, in place

# name: (layer sizes, chain_walker_planes kwargs). For float32 the rule
# (genome_rows) reads b0 w0 b1 w1 of the first in place and cuts b2 w2, all
# of the second in place (for bfloat16 too), nothing of the third.
GENOME_SHAPES = {
    "244x64x64x17": ((244, 64, 64, 17), {}),
    "aligned-64x16x32x16": (
        (64, 16, 32, 16), dict(n_masses=18, act_dim=16, obs_dim=64),
    ),
    "unaligned-244x12x10x17": ((244, 12, 10, 17), {}),
}


def _genome_case(shape, n, ep, T=3):
    """A seeded flat genome ``(dim, n)`` in ``mlp_policy``'s own order, the
    same numbers as per-layer planes, and the walker's initial planes."""
    from evox_tpu.utils.common import leaf_offsets

    sizes, env_kw = GENOME_SHAPES[shape]
    penv = chain_walker_planes(max_steps=T, **env_kw)
    keys = jax.random.split(jax.random.PRNGKey(0), ep)
    env0 = jax.vmap(penv.base.reset)(keys)
    planes0 = penv.to_planes(jax.tree.map(
        lambda x: jnp.broadcast_to(x[:, None], (ep, n) + x.shape[1:]).reshape(
            (ep * n,) + x.shape[1:]
        ),
        env0,
    ))
    init_params, _ = mlp_policy(sizes)
    offsets, dim = leaf_offsets(init_params(jax.random.PRNGKey(0)))
    genome = 0.2 * jax.random.normal(jax.random.PRNGKey(7), (dim, n))
    weights = tuple(
        genome[o["w"] : o["w"] + i * j].reshape(i, j, n)
        for o, i, j in zip(offsets, sizes, sizes[1:])
    )
    biases = tuple(
        genome[o["b"] : o["b"] + j] for o, j in zip(offsets, sizes[1:])
    )
    return penv, planes0, sizes, offsets, genome, weights, biases


def _cut(leaves, rows, which):
    return tuple(
        None if r[which] is not None else x for x, r in zip(leaves, rows)
    )


def _genome_cross():
    """Every combination. Tier-1 runs, beside each shape and each n, one
    setting of the four switches and its opposite (so each switch is seen
    both ways); the rest are marked slow."""
    import itertools

    ns, tier1 = (128, 130, 256), ((1, 0, 0, 0), (0, 0, 1, 0), (1, 1, 1, 0))
    for shape, n, early_stop, ep, dtype, linear in itertools.product(
        GENOME_SHAPES, ns, (True, False), (1, 2), (None, "bfloat16"), ((), (0,))
    ):
        switches = (int(early_stop), ep - 1, int(dtype is not None), len(linear))
        base = tier1[(list(GENOME_SHAPES).index(shape) + ns.index(n)) % 3]
        fast = switches in (base, tuple(1 - b for b in base))
        yield pytest.param(
            shape, n, early_stop, ep, dtype, linear,
            marks=() if fast else pytest.mark.slow,
            id=f"{shape}-n{n}-{'while' if early_stop else 'fori'}-ep{ep}"
               f"-{dtype or 'f32'}-lin{len(linear)}",
        )


@pytest.mark.parametrize(
    "shape,n,early_stop,ep,dtype,linear", list(_genome_cross())
)
def test_flat_genome_bit_identical_to_per_layer(
    shape, n, early_stop, ep, dtype, linear
):
    """The kernel handed the flat genome, reading in place what
    ``genome_rows`` says it may and taking the rest as blocks, returns the
    per-layer call's totals bit for bit."""
    from evox_tpu.kernels.rollout_mlp import genome_rows

    penv, planes0, sizes, offsets, genome, weights, biases = _genome_case(
        shape, n, ep
    )
    kw = dict(
        T=3, sizes=sizes, step_planes=penv.step_planes,
        obs_planes=penv.obs_planes, episodes=ep, early_stop=early_stop,
        interpret=True, weight_dtype=dtype and jnp.dtype(dtype), linear=linear,
    )
    rows = genome_rows(offsets, sizes, dtype or genome.dtype)
    want = fused_mlp_rollout(weights, biases, dict(planes0), **kw)
    got = fused_mlp_rollout(
        _cut(weights, rows, 0), _cut(biases, rows, 1), dict(planes0),
        genome=genome, rows=rows, **kw,
    )
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flat_genome_unaligned_rows_in_place():
    """Row ranges off the sublane tiling are read in place too (the rule
    leaves them cut because of what they cost on the chip, not because
    they are wrong): every leaf of 244-64-64-17 in place, no block but the
    genome, the same totals bit for bit."""
    penv, planes0, sizes, offsets, genome, weights, biases = _genome_case(
        "244x64x64x17", 130, 1
    )
    kw = dict(
        T=3, sizes=sizes, step_planes=penv.step_planes,
        obs_planes=penv.obs_planes, interpret=True,
    )
    rows = tuple((o["w"], o["b"]) for o in offsets)
    assert rows[2] == (19857, 19840)
    none = (None,) * 3
    want = fused_mlp_rollout(weights, biases, dict(planes0), **kw)
    got = fused_mlp_rollout(none, none, dict(planes0), genome=genome, rows=rows, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a leaf comes as a block or has its row, never both and never neither
    with pytest.raises(ValueError, match="one of the two"):
        fused_mlp_rollout(weights, biases, dict(planes0), genome=genome, rows=rows, **kw)
    with pytest.raises(ValueError, match="one of the two"):
        fused_mlp_rollout(none, none, dict(planes0), **kw)


def test_genome_rows_rule_and_analysis():
    """The rule on the cell's own shape: ``b0 w0 b1 w1`` in place, the
    17-wide layer cut, for float32 and for bfloat16; and the analysis
    states the same rows and the residency of what the kernel is handed."""
    from evox_tpu.kernels import fused_rollout_analysis
    from evox_tpu.kernels.rollout_mlp import genome_rows

    sizes = (244, 64, 64, 17)
    init_params, _ = mlp_policy(sizes)
    params = init_params(jax.random.PRNGKey(0))
    adapter = TreeAndVector(params)
    assert adapter.offsets == [
        {"b": 0, "w": 64}, {"b": 15680, "w": 15744}, {"b": 19840, "w": 19857}
    ]
    want = ((64, 0), (15744, 15680), (None, None))
    assert genome_rows(adapter.offsets, sizes, jnp.float32) == want
    assert genome_rows(adapter.offsets, sizes, jnp.bfloat16) == want
    # a leaf whose first row is a multiple of 8 and not of 16
    moved = [{"b": 8, "w": 72}] + adapter.offsets[1:]
    assert genome_rows(moved, sizes, jnp.float32)[0] == (72, 8)
    assert genome_rows(moved, sizes, jnp.bfloat16)[0] == (None, None)

    ws = tuple(jnp.zeros((i, j, 128)) for i, j in zip(sizes, sizes[1:]))
    bs = tuple(jnp.zeros((j, 128)) for j in sizes[1:])
    report = fused_rollout_analysis(ws, bs, params=params)
    assert (report["rows_in_place"], report["rows_cut"]) == (19840, 1105)
    assert report["resident_bytes_per_cell"] == (20945 + 1105) * 128 * 4
    assert report["headroom_bytes"] > 0
    bf16 = fused_rollout_analysis(ws, bs, weight_dtype=jnp.bfloat16, params=params)
    assert bf16["rows_in_place"] == 19840
    assert bf16["resident_bytes_per_cell"] * 2 == report["resident_bytes_per_cell"]
    assert "rows_in_place" not in fused_rollout_analysis(ws, bs)


# ------------------------------------------- through the workflow, engaged


def _walker_workflow(sizes, decode_of, mesh=None, island=False, pop=16, **env_kw):
    """OpenES on the fused-planes walker as the benchmark's builder makes
    it; ``decode_of(adapter)`` is the workflow's one pop transform."""
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.utils import rank_based_fitness

    penv = chain_walker_planes(max_steps=4, **env_kw)
    init_params, apply = mlp_policy(sizes)
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    prob = PolicyRolloutProblem(
        apply, penv.base, num_episodes=1, stochastic_reset=False,
        fused_planes=penv, fused_interpret=True,
    )
    center = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (adapter.dim,))
    algo = OpenES(center, pop, learning_rate=0.05, noise_stdev=0.05)
    return StdWorkflow(
        algo, prob, opt_direction="max",
        pop_transforms=(decode_of(adapter),),
        fit_transforms=(rank_based_fitness,),
        mesh=mesh, eval_shard_map=island,
    )


def _plain(adapter):
    return adapter.batched_to_tree


def _wrapped(adapter):
    return lambda x: adapter.batched_to_tree(x)


@pytest.mark.parametrize("island", [False, True], ids=["one-device", "shard-map"])
def test_workflow_flat_genome_bit_identical_to_tree_path(island):
    """Three generations of ``StdWorkflow.run`` end in the same state, bit
    for bit, with the plain ``batched_to_tree`` (the problem is handed the
    genome, ``w0 b0 w1 b1`` of 244-16-8-17 read in place) and with the same
    transform wrapped in a lambda (every layer cut, as ever); also per
    shard under the explicit evaluation island."""
    from evox_tpu.core.distributed import create_mesh

    mesh = create_mesh() if island else None
    finals = []
    for decode_of, engaged in ((_plain, True), (_wrapped, False)):
        wf = _walker_workflow((244, 16, 8, 17), decode_of, mesh, island)
        assert (wf._decode_adapter is not None) == engaged
        state = wf.run(wf.init(jax.random.PRNGKey(1)), 3)
        assert int(state.generation) == 3
        finals.append(jax.tree.leaves(state))
    for a, b in zip(*finals):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _produced_shapes(jaxpr, into):
    """Shapes of everything any equation of ``jaxpr`` produces, sub-jaxprs
    (jit, loops, the kernel's body) included."""
    for eqn in jaxpr.eqns:
        into.update(getattr(v.aval, "shape", None) for v in eqn.outvars)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _produced_shapes(sub, into)
    return into


def test_flat_genome_step_never_cuts_the_in_place_layers():
    """The guard against the cut coming back: in the engaged step's jaxpr,
    dead code dropped, no equation produces an in-place layer as an array
    of its own, in any of the layouts it has had ((n, in * out), (n, in,
    out), (in, out, n), (in * out, n)); the step whose decode is wrapped
    (not engaged) does produce them, so the guard can see one."""
    from jax._src.interpreters import partial_eval as pe

    sizes, n = (64, 16, 32, 16), 16
    leaves = set()
    for i, j in zip(sizes, sizes[1:]):
        leaves |= {(n, i * j), (n, i, j), (i, j, n), (i * j, n)}

    def shapes(decode_of):
        wf = _walker_workflow(
            sizes, decode_of, pop=n, n_masses=18, act_dim=16, obs_dim=64
        )
        state = jax.eval_shape(wf.init, jax.random.PRNGKey(1)).replace(first_step=False)
        closed = jax.make_jaxpr(wf._step_impl)(state)
        jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
        return _produced_shapes(jaxpr, set())

    assert not (shapes(_plain) & leaves)
    assert shapes(_wrapped) & leaves
