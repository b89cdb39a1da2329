"""Compiles for a described TPU v5e — the only file that describes the chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). Interpret-mode
tests cannot see what Mosaic refuses (block shapes off the (8, 128) tiling,
float iotas, too much VMEM) or what the SPMD partitioner inserts; these
compiles can, at real widths, at no chip time. Nothing runs: a compile that
passes is not a chip run.

Only one process may load the TPU's library, so the topology is described
inside a module-scoped fixture (never at import, never ``autouse``, never in
``conftest.py``), every compile happens in this process, and every such test
lives in this one file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.pso import CSO
from evox_tpu.core.distributed import POP_AXIS, state_sharding
from evox_tpu.problems.numerical import Ackley


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip would be written to the persistent
    cache but cannot be read back without a chip (the next one warns and
    compiles again): keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _shapes_on(tree, sharding):
    """The tree's shapes, placed (there is no device to hold an array):
    ``sharding`` is one sharding for every leaf or a matching tree of them."""
    if isinstance(sharding, jax.sharding.Sharding):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sharding
    )


# ------------------------------------------------------------------ kernels


def _walker_kernel(n=16384, hidden=64, T=100, dtype=None):
    """``fused_mlp_rollout`` at 244-64-64-17 through the problem that calls it."""
    from evox_tpu.kernels.rollout_mlp import chain_walker_planes
    from evox_tpu.problems.neuroevolution import PolicyRolloutProblem, mlp_policy

    penv = chain_walker_planes(max_steps=T)
    env = penv.base
    init_params, apply = mlp_policy((env.obs_dim, hidden, hidden, env.act_dim))
    prob = PolicyRolloutProblem(
        apply, env, num_episodes=1, stochastic_reset=False,
        fused_planes=penv, fused_interpret=False, fused_planes_dtype=dtype,
    )
    pop = jax.eval_shape(
        lambda k: jax.vmap(init_params)(jax.random.split(k, n)), jax.random.PRNGKey(0)
    )
    return prob.evaluate, (jax.eval_shape(prob.init, jax.random.PRNGKey(0)), pop)


def _walker_kernel_genome(n=16384, hidden=64, T=100, dtype=None):
    """The same kernel as a workflow with the plain decode calls it: handed
    the flat genome (``dim`` 20,945, one ``(20945, 128)`` block a cell),
    ``b0 w0 b1 w1`` read in place, the 17-wide layer cut. ``dtype``
    bfloat16: the genome converted whole and read under the 16-row rule,
    the benchmark's low-precision control."""
    from evox_tpu.utils import TreeAndVector

    evaluate, (state, pop) = _walker_kernel(n, hidden, T, dtype)
    adapter = TreeAndVector(jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype), pop))
    genome = jax.ShapeDtypeStruct((n, adapter.dim), jnp.float32)
    problem = evaluate.__self__
    fn = lambda s, p, g: problem.evaluate_genome(s, p, g, adapter)  # noqa: E731
    return fn, (state, pop, genome)


def _pendulum_kernel(n=65536, episodes=2, hidden=16, T=200):
    """``fused_rollout`` at hidden 16 through the problem that calls it."""
    from evox_tpu.kernels.rollout import pendulum_soa
    from evox_tpu.problems.neuroevolution import PolicyRolloutProblem, flat_mlp_policy

    soa = pendulum_soa(max_steps=T)
    apply, dim = flat_mlp_policy(soa.base.obs_dim, hidden, soa.base.act_dim)
    prob = PolicyRolloutProblem(
        apply, soa.base, num_episodes=episodes, stochastic_reset=False,
        early_exit=False, fused_env=soa, fused_interpret=False,
    )
    pop = jax.ShapeDtypeStruct((n, dim), jnp.float32)
    return prob.evaluate, (jax.eval_shape(prob.init, jax.random.PRNGKey(0)), pop)


def _topk_kernel(n=4096, k=128):
    # the (20000, 1000) and (65536, 1024) shapes compile too, in ~25 s each:
    # a builder's rehearsal, not the suite's
    from evox_tpu.kernels.topk import partial_topk

    fn = lambda v: partial_topk(v, k, use_kernel=True)  # noqa: E731
    return fn, (jax.ShapeDtypeStruct((n,), jnp.float32),)


def _dominance_kernel(n=20000, m=3):
    """``packed_dominance`` as the chip runs it: the backend chooses the
    ``dominance_pack`` kernel (the test says the backend is the TPU's)."""
    from evox_tpu.kernels.dominance import packed_dominance

    return packed_dominance, (jax.ShapeDtypeStruct((n, m), jnp.float32),)


def _flash_kernel(members=2, t=2048, heads=16, nope=128, rope=64, v=128):
    """``flash_attention`` at the language-model cell's shapes: two members a
    call, MLA's 192-wide queries and keys, 128-wide values, bfloat16."""
    from evox_tpu.kernels.flash_attention import flash_attention, flash_block_sizes

    bq, bk = flash_block_sizes(t, nope, v)
    fn = lambda qn, qr, kv, kr, doc, first, last: flash_attention(  # noqa: E731
        qn, qr, kv, kr, doc, (first, last), heads=heads, scale=(nope + rope) ** -0.5,
        block_q=bq, block_k=bk, interpret=False,
    )
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return fn, (bf16(members, t, heads * nope), bf16(members, heads, t, rope),
                bf16(members, t, heads * (nope + v)), bf16(members, t, rope),
                i32(t), i32(t // bq), i32(t // bq))


def _gqa_kernel(members=2, t=8192, heads=32, kv_heads=8, width=64):
    """``gqa_flash_attention`` at the third language-model cell's shapes: two
    members a call, a row of 8,192 tokens, 32 query heads on 8 key-value heads
    of 64, bfloat16."""
    from evox_tpu.kernels.gqa_flash_attention import gqa_block_sizes, gqa_flash_attention

    bq, bk = gqa_block_sizes(t, heads, kv_heads, width)
    fn = lambda q, k, v, doc, first, last: gqa_flash_attention(  # noqa: E731
        q, k, v, doc, (first, last), heads=heads, kv_heads=kv_heads, scale=width**-0.5,
        block_q=bq, block_k=bk, interpret=False,
    )
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return fn, (bf16(members, t, heads * width), bf16(members, t, kv_heads * width), bf16(members, t, kv_heads * width),
                i32(t), i32(t // bq), i32(t // bq))


def _kda_kernel(members=2, t=2048, heads=32, width=128):
    """``kda_scan`` at the hybrid language-model cell's shapes: two members a
    call, 32 heads of 128 keys and values, bfloat16 operands, float32 decay."""
    from evox_tpu.kernels.kda_scan import kda_scan

    fn = lambda q, k, v, g, beta, doc: kda_scan(q, k, v, g, beta, doc, heads=heads)  # noqa: E731
    wide = jax.ShapeDtypeStruct((members, t, heads * width), jnp.bfloat16)
    return fn, (wide, wide, wide, jax.ShapeDtypeStruct(wide.shape, jnp.float32),
                jax.ShapeDtypeStruct((members, t, heads), jnp.float32), jax.ShapeDtypeStruct((t,), jnp.int32))


def _kda_conv_kernel(normalise, members=2, t=2048, heads=32, width=128):
    """``kda_conv`` at the hybrid language-model cell's shapes: a projection of
    two members as ``linear`` leaves it, bfloat16, each member's four taps."""
    from evox_tpu.kernels.kda_conv import kda_conv

    fn = lambda u, w, pos: kda_conv(u, w, pos, width=width, normalise=normalise)  # noqa: E731
    return fn, (jax.ShapeDtypeStruct((members, t, heads * width), jnp.bfloat16),
                jax.ShapeDtypeStruct((members, 4, heads * width), jnp.float32), jax.ShapeDtypeStruct((t,), jnp.int32))


KERNELS = {
    "fused_mlp_rollout-244x64x64x17-n16384-T100": _walker_kernel,
    "fused_mlp_rollout-genome20945-n16384-T100": _walker_kernel_genome,
    "fused_mlp_rollout-genome20945-bf16-n16384-T100": functools.partial(
        _walker_kernel_genome, dtype=jnp.bfloat16
    ),
    "fused_rollout-h16-n65536x2-T200": _pendulum_kernel,
    "partial_topk-n4096-k128": _topk_kernel,
    "packed_dominance-n20000-m3": _dominance_kernel,
    "packed_dominance-n100000-m3": functools.partial(_dominance_kernel, 100_000),  # the NSGA-II cell's merged n
    "flash_attention-m2-h16-t2048-qk192-v128": _flash_kernel,
    "gqa_flash_attention-m2-h32-kv8-t8192-d64": _gqa_kernel,
    # the looped model's: a key-value head of 128 is a grid cell's whole tile
    "gqa_flash_attention-m2-h16-kv16-t2048-d128": functools.partial(_gqa_kernel, t=2048, heads=16, kv_heads=16,
                                                                    width=128),
    "kda_scan-m2-h32-t2048-k128-v128": _kda_kernel,  # two heads a grid cell, joined: (128, 128) products
    "kda_scan-m2-h3-t512-k128-v128": functools.partial(_kda_kernel, t=512, heads=3),  # an odd count: a head a cell
    "kda_conv-m2-h32-t2048-w128-none": functools.partial(_kda_conv_kernel, None),
    "kda_conv-m2-h32-t2048-w128-l2": functools.partial(_kda_conv_kernel, "l2"),
    "kda_conv-m2-h32-t2048-w128-l2_scaled": functools.partial(_kda_conv_kernel, "l2_scaled"),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache, monkeypatch):
    """Mosaic accepts the kernel at its real width, and it IS the kernel:
    the compiled text holds the custom call (an envelope that hands the
    shape to the XLA path must never pass for the kernel). Code that asks
    the backend is told it is the TPU's, as on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = KERNELS[name]()
    compiled = jax.jit(fn).lower(*_shapes_on(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_flat_genome_reaches_the_kernel_uncut(one_chip, no_persistent_cache):
    """The chip's compiler hands the kernel the genome itself: the custom
    call takes ``f32[20945,n]``, and no instruction of the program makes
    ``w0`` or ``w1`` an array of its own (the per-layer call's does)."""
    n = 16384
    cut = ("f32[244,64,%d]" % n, "f32[64,64,%d]" % n, "f32[%d,15616]" % n, "f32[%d,4096]" % n)
    fn, args = _walker_kernel_genome(n)
    text = jax.jit(fn).lower(*_shapes_on(args, one_chip)).compile().as_text()
    (call,) = [l for l in text.splitlines() if "tpu_custom_call" in l and " custom-call(" in l]
    assert "f32[20945,%d]" % n in call.split("operand_layout_constraints=")[1]
    assert not any(shape in text for shape in cut)
    fn, args = _walker_kernel(n)
    text = jax.jit(fn).lower(*_shapes_on(args, one_chip)).compile().as_text()
    assert any(shape in text for shape in cut)


def test_dominance_matrix_is_written_once_on_the_chip(one_chip, no_persistent_cache, monkeypatch):
    """At the NSGA-II cell's merged n = 100,000 the chip's build writes the
    packed ``(3125, 100000)`` matrix once, in the layout the peel reads: no
    stacked slabs (``[25,128,100000]``), no padded words (``[3200,100000]``),
    no copy or slice whose result is the matrix, and temporaries far under
    the 3.84 GB of the ``lax.map`` build it replaced (PR 37)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = _dominance_kernel(100_000)
    compiled = jax.jit(fn).lower(*_shapes_on(args, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "[25,128,100000]" not in text and "[3200,100000]" not in text
    assert not re.search(r"\[3125,100000\]\{[^}]*\} (copy|slice)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 500_000_000


def test_topk_outside_envelope_is_not_the_kernel(one_chip, no_persistent_cache):
    """``k > block_size`` goes to the XLA path even under ``use_kernel=True``
    (a tested contract of kernels/topk.py). At NSGA-II's own shape — pop
    10,000, merged n 20,000, k 10,000 — the compiled program therefore holds
    no kernel: an A/B of ``use_kernel`` there compares XLA with XLA."""
    from evox_tpu.kernels.topk import partial_topk

    # a small n keeps this a one-second compile; the envelope is on k alone
    fn = lambda v: partial_topk(v, 1280, use_kernel=True)  # noqa: E731
    arg = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" not in jax.jit(fn).lower(arg).compile().as_text()


# ------------------------------------------------------- the language model


def _routed_path():
    """The router and ``held_experts`` of one expert layer at the cell's
    shapes: a chunk of 4 pairs, 16,384 tokens, 98,304 assignments."""
    from benchmark.lib import manifest as mf
    from evox_tpu.core.lowrank import tree_factors
    from evox_tpu.problems.lm import LMConfig
    from evox_tpu.problems.lm import model as lm

    _, _, config, traffic = mf.cell_parts(mf.load(), "moonlight_es_pop64_seq2k")
    cfg, blocks = LMConfig.from_dict(config), config["blocks"]
    pairs = blocks["chunk_pairs"]
    layer = lm.param_shapes(cfg)["layers"][cfg.first_k_dense_replace]
    center = {k: layer[k] for k in ("router", "router_bias", "experts")}
    f32 = jax.tree.map(lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32), center, is_leaf=lm._is_shape)
    factors = jax.eval_shape(lambda c: tree_factors(jax.random.PRNGKey(0), c, pairs, int(config["rank"])), f32)
    p = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16 if len(v.shape) > 1 else v.dtype), f32)
    xn = jax.ShapeDtypeStruct((pairs, 2, int(traffic["seq_len"]), cfg.hidden_size), jnp.bfloat16)

    def fn(p, f, xn):
        idx, w = lm.route(cfg, p, f, jnp.float32(1e-3), xn)
        return lm.held_experts(cfg, p, f, jnp.float32(1e-3), xn, idx, w, blocks["expert_block_rows"])

    return fn, (p, factors, xn)


def test_routed_path_keeps_no_row_for_every_assignment_on_the_chip(one_chip, no_persistent_cache):
    """The chip's compiler, for a chunk of the language-model cell: what the
    routed path keeps beside its arguments and its output is the tokens'
    float32 sums (16,384 x 2,048 x 4 B = 134 MB) and little more. The path
    that wrote every assignment's row and gathered it back kept 1,481,524,736
    B (PR 31's compile of its parent)."""
    fn, args = _routed_path()
    stats = jax.jit(fn).lower(*_shapes_on(args, one_chip)).compile().memory_analysis()
    assert stats.temp_size_in_bytes < 150_000_000, stats


def test_routed_path_gathers_no_score_on_the_chip(one_chip, no_persistent_cache):
    """The chip's compiler, for the same chunk: no gather reads the router's
    scores (an operand of ``(..., n_routed_experts)``). The chosen scores are
    a compare and select over the experts; a gather of them was a serial
    loop of 15 ns a scalar on the chip (PERF.md section 5). The token gather of
    the experts' loop reads rows of ``hidden`` and stays."""
    fn, args = _routed_path()
    width = args[0]["router"].shape[-1]
    text = jax.jit(fn).lower(*_shapes_on(args, one_chip)).compile().as_text()
    shapes = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", text))
    operands = [tuple(int(n) for n in shapes[name].split(",") if n)
                for name in re.findall(r"\bgather\(%([\w.-]+)", text)]
    assert operands, "the token gather is missing: the pattern no longer reads the compiled text"
    assert not [o for o in operands if o and o[-1] == width], operands


def test_language_model_cell_compiles_for_v5e(topo, no_persistent_cache, monkeypatch):
    """The cell's steady run loop at its real shapes, with the body the chip
    takes (``forward`` asks the backend, steered here): the attention kernel
    is there, once a layer, and the temporaries stay under what they were
    while the routed path kept a row for every assignment (2,752,349,184 B
    at PR 31; the bfloat16 copy of the centre is 1.69 GB of them)."""
    from benchmark import rehearse
    from benchmark.lib import manifest as mf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = rehearse.rehearse(mf.load(), "moonlight_es_pop64_seq2k", topo)
    assert got["custom_calls"] == 5, got
    assert got["temp_bytes_per_device"] < 4_258_132_480, got  # the parent of PR 31, which wrote every assignment's row


def test_lfm2_cell_compiles_for_v5e(topo, no_persistent_cache, monkeypatch):
    """The third language-model cell's steady run loop at its real shapes
    (rows of 8,192 tokens, a pair a chunk), with the bodies the chip takes:
    the grouped-query kernel is there, once (one attention layer of five; the
    plain body's scores of one pair would be 17.2 GB), and the program fits
    the chip: 10,578,376,704 B at PR 34, of which the float32 centre twice."""
    from benchmark import rehearse
    from benchmark.lib import manifest as mf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = rehearse.rehearse(mf.load(), "lfm2_es_pop16_seq8k", topo)
    assert got["custom_calls"] == 1, got
    assert 0.25 * 16e9 < got["total_bytes_per_device"] < 16e9, got


# --------------------------------------------------------------- four chips


def _cso_step(mesh, pop=4096, dim=1024):
    """The steady (``first_step=False``) CSO / Ackley step program and its
    state's shapes; the init-generation peel of a row-wise problem rightly
    holds no collective."""
    algo = CSO(lb=-32.0 * jnp.ones(dim), ub=32.0 * jnp.ones(dim), pop_size=pop)
    wf = StdWorkflow(algo, Ackley(), mesh=mesh)
    state = jax.eval_shape(wf.init, jax.random.PRNGKey(0)).replace(first_step=False)
    return jax.jit(wf._step_impl), state


def test_sharded_cso_step_compiles_for_four_chips(topo, one_chip, no_persistent_cache):
    """The population-sharded step partitions for a 2x2 v5e: the pair
    shuffle crosses shards, so the text holds collectives, and each device
    is handed about a quarter of the single-device program's arguments."""
    mesh = Mesh(np.array(topo.devices), (POP_AXIS,))
    step4, state = _cso_step(mesh)
    compiled4 = step4.lower(_shapes_on(state, state_sharding(state, mesh))).compile()
    text = compiled4.as_text()
    assert any(
        c in text for c in ("all-gather", "all-reduce", "collective-permute", "all-to-all")
    )

    step1, state1 = _cso_step(None)
    compiled1 = step1.lower(_shapes_on(state1, one_chip)).compile()
    per_device = compiled4.memory_analysis().argument_size_in_bytes
    whole = compiled1.memory_analysis().argument_size_in_bytes
    assert whole >= 2 * 4096 * 1024 * 4  # population and velocity, float32
    assert 0.2 < per_device / whole < 0.3, (per_device, whole)
