"""Plain reference of the ``lfm2_24b_a2b_es`` configuration.

Low-rank OpenES (mirrored pairs, z-scored fitness, plain SGD on the centre)
over a language model whose layers mix tokens by a gated short convolution
(computed here as a plain sum of shifted copies) or by grouped-query attention
with a per-head RMS norm of q and k and RoPE (plain masked softmax, **a block
of queries at a time** so that a row's scores fit), over a mixture of experts
with no shared expert (``lfm2_moe``), written in straightforward ``jax.numpy``
at float32 with ``highest`` matmul precision. There is no factor form here:
for every member the dense ``W + sign * sigma / sqrt(rank) * A @ B.T`` of each
matrix is **materialised**, and the member's plain forward pass runs on those
weights, a member at a time. It imports nothing of the program and draws
everything from the seed.

What it shares with the program is the semantics, and the order in which keys
are folded and split, because the random draws are part of the semantics:

- workflow: ``k_algo, k_prob = split(key(seed))``;
- centre: the tree of ``_shapes``; leaf ``l`` of ``jax.tree.leaves``: a leaf
  of two or three axes (the convolutions' ``(hidden, taps)`` among them) is
  ``init_std * normal(fold_in(fold_in(key, 1), l))``, a norm gain one, the
  router's expert bias zero;
- the search: ``akey, _ = split(k_algo)`` at init; each generation ``akey, k
  = split(akey)``; for leaf ``l`` with two axes ``(d_in, d_out)`` (three: a
  stack of matrices) and pair ``p``: ``Z = normal(fold_in(fold_in(k, l), p),
  ([n,] d_in + d_out, rank))``, ``A = Z[:d_in]``, ``B = Z[d_in:]``; member
  ``p`` is the ``+`` sign, member ``p + pop / 2`` the ``-``; fitness
  (minimised) z-scored over the population; ``grad = 1 / (pop * sigma) *
  sum_p (f_p+ - f_p-) * sigma / sqrt(rank) * A_p @ B_p.T``; ``centre -= lr *
  grad``; leaves with fewer axes stay;
- the batch of generation ``g`` (from 0): ``fold_in(k_prob, g)`` split in
  two; document lengths ``clip(round(exp(log(median) + sigma * normal)),
  min, T)`` packed until the row is full; ids uniform over the held rows of
  the vocabulary; every member reads it;
- the member model: the docstring of ``_forward``.

The share of the deployment: the expert layers route over all
``num_experts_published`` experts and add only the experts ``experts_held``
(there is no shared expert: the layer's output is that part alone); the
vocabulary is the held rows; the layers are ``layers_held`` of
``layer_types``, which counts from 0, those under ``num_dense_layers`` dense.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int):
    seed = int(seed)
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)


def _layers(c: dict) -> list:
    """``(kind, dense)`` of each layer held: ``layer_types`` counts from 0."""
    first, last = c["layers_held"]
    return [(c["layer_types"][l], l < c["num_dense_layers"]) for l in range(first, last)]


def _head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def _shapes(c: dict) -> dict:
    d, h, kv, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], _head_dim(c)
    held = c["experts_held"][1] - c["experts_held"][0]

    def mlp(width, stack=()):
        return {"gate": stack + (d, width), "up": stack + (d, width), "down": stack + (width, d)}

    layers = []
    for kind, dense in _layers(c):
        layer = {"mlp_norm": (d,)}
        if kind == "conv":
            layer["conv"] = {"norm": (d,), "in_proj": (d, 3 * d), "taps": (d, c["conv_L_cache"]), "out_proj": (d, d)}
        else:
            layer["gqa"] = {"norm": (d,), "q": (d, h * hd), "k": (d, kv * hd), "v": (d, kv * hd),
                            "q_norm": (hd,), "k_norm": (hd,), "o": (h * hd, d)}
        if dense:
            layer["mlp"] = mlp(c["intermediate_size"])
        else:
            layer["router"] = (d, c["num_experts_published"])
            layer["router_bias"] = (c["num_experts_published"],)
            layer["experts"] = mlp(c["moe_intermediate_size"], (held,))
        layers.append(layer)
    return {"embed": (c["vocab_size"], d), "layers": layers, "final_norm": (d,),
            "head": (d, c["vocab_size"])}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(n, int) for n in x)


def _init(c: dict, key) -> dict:
    paths, treedef = jax.tree.flatten_with_path(_shapes(c), is_leaf=_is_shape)
    leaves = []
    for l, (path, shape) in enumerate(paths):
        if len(shape) >= 2:
            leaves.append(c["init_std"] * jax.random.normal(jax.random.fold_in(key, l), shape))
        elif path[-1].key == "router_bias":
            leaves.append(jnp.zeros(shape))
        else:
            leaves.append(jnp.ones(shape))
    return jax.tree.unflatten(treedef, leaves)


def _factors(k, l: int, shape: tuple, p: int, rank: int):
    """``(A, B)`` of pair ``p`` for leaf ``l``, or None where it stays."""
    if len(shape) not in (2, 3):
        return None
    d_in, d_out = shape[-2:]
    z = jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(k, l), p), shape[:-2] + (d_in + d_out, rank)
    )
    return z[..., :d_in, :], z[..., d_in:, :]


def _batch(k_prob, g: int, c: dict, t: dict):
    seq, low = int(t["seq_len"]), int(t["doc_len_min"])
    k_len, k_ids = jax.random.split(jax.random.fold_in(k_prob, g))
    z = jax.random.normal(k_len, (-(-seq // low),))
    lens = jnp.clip(jnp.round(jnp.exp(math.log(t["doc_len_median"]) + t["doc_len_sigma"] * z)), low, seq)
    ends = np.cumsum(np.asarray(lens).astype(np.int64))
    at = np.arange(seq)
    doc = np.searchsorted(ends, at, side="right")
    pos = at - np.concatenate([[0], ends])[doc]
    ids = jax.random.randint(k_ids, (seq,), 0, c["vocab_size"], dtype=jnp.int32)
    return ids, jnp.asarray(doc, jnp.int32), jnp.asarray(pos, jnp.int32)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _squares(x, first):
    """The sum of squares of ``x`` ``(T, hidden)`` over every token, and over
    the tokens ``first`` alone."""
    by_token = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1)
    return jnp.stack([jnp.sum(by_token), jnp.sum(jnp.where(first, by_token, 0.0))])


def _conv(c: dict, a: dict, xn, pos):
    """One member's gated short convolution on the normed ``xn`` ``(T,
    hidden)``: the output before the residual. ``[B, C, u] = xn W_in``, a
    third each in that order; ``z = B * u``; ``c_t = sum_j w[:, j] * z_{t -
    (taps - 1) + j}``, depthwise and causal, the last tap on the token itself,
    no bias, a tap that reaches before the token's document began reading
    zero; ``(C * c) W_out``. No activation."""
    d, taps = xn.shape[-1], c["conv_L_cache"]
    bcu = xn @ a["in_proj"]
    gate_b, gate_c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    z = gate_b * u
    y = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j
        past = jnp.where((pos >= back)[:, None], jnp.roll(z, back, axis=0), 0)
        y = y + past * a["taps"][:, j]
    return (gate_c * y) @ a["out_proj"]


def _rope(x, pos, theta):
    """``x`` ``(T, heads, head_dim)``: the two halves of the head paired,
    positions counted from the start of the token's document."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = (f(angle)[:, None, :].astype(x.dtype) for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(c: dict, a: dict, xn, doc, pos):
    """One member's grouped-query attention on the normed ``xn``: ``q`` as
    ``num_attention_heads`` heads, ``k`` and ``v`` as ``num_key_value_heads``,
    of ``hidden / heads`` each; ``q`` and ``k`` through an RMS norm over each
    head (gains ``q_norm``, ``k_norm``, shared by the heads) and then RoPE
    over the whole head; query head ``h`` reads key-value head ``h // (heads
    / kv heads)``; scores ``q.k / sqrt(head_dim)``, causal and within a
    document, softmax; the heads' outputs through ``Wo``. A block of queries
    against every key at a time: the whole row's scores would be ``heads * T *
    T`` floats."""
    t, dtype = xn.shape[0], xn.dtype
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], _head_dim(c)
    eps, theta = c["norm_eps"], c["rope_parameters"]["rope_theta"]
    q = _rope(_norm((xn @ a["q"]).reshape(t, h, hd), a["q_norm"], eps), pos, theta)
    k = _rope(_norm((xn @ a["k"]).reshape(t, kv, hd), a["k_norm"], eps), pos, theta)
    v = (xn @ a["v"]).reshape(t, kv, hd)
    block = max(b for b in range(1, min(t, 512) + 1) if t % b == 0)
    at = jnp.arange(t)

    def rows(i):
        mine = jax.lax.dynamic_slice_in_dim(at, i * block, block)
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block).reshape(block, kv, h // kv, hd)
        s = jnp.einsum("qgjd,kgd->gjqk", qi, k) / math.sqrt(hd)
        mask = (mine[:, None] >= at[None, :]) & (doc[mine][:, None] == doc[None, :])
        s = jnp.where(mask, s, jnp.finfo(dtype).min)
        o = jnp.einsum("gjqk,kgd->qgjd", jax.nn.softmax(s, axis=-1).astype(dtype), v)
        return o.reshape(block, h * hd)

    return jax.lax.map(rows, jnp.arange(t // block)).reshape(t, h * hd) @ a["o"]


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def _forward(c: dict, w: dict, ids, doc, pos, n_probe: int):
    """One member on its own dense weights ``w``: ``(loss, the logits at the
    last n_probe positions, held assignments for each expert layer, for each
    convolution layer the sums of squares of the mixer's output and of its
    normed input, each over every token and over the first taps - 1 tokens of
    the documents alone: (2, 2))``.

    Pre-norm residual blocks, RMSNorm (``norm_eps``), final norm, untied
    head. A ``conv`` layer: ``_conv``. A ``full_attention`` layer:
    ``_attention``. MLPs ``down(silu(gate x) * up x)``. Router: ``s =
    sigmoid(x Wr)`` over all the published experts, the ``num_experts_per_tok``
    largest of ``s + expert bias``, weights ``routed_scaling_factor * s_e /
    (sum of the chosen s + 1e-6)``; the layer adds the chosen experts held
    here and nothing else (no shared expert). Loss: mean next-token negative
    log-likelihood, every position but the first of each document."""
    eps, t = c["norm_eps"], ids.shape[0]
    lo, hi = c["experts_held"]
    at = jnp.arange(t)
    x = w["embed"][ids]
    first = pos < c["conv_L_cache"] - 1  # the tokens a tap of which reaches before their document began
    held, squares = [], []
    for layer in w["layers"]:
        if "conv" in layer:
            xn = _norm(x, layer["conv"]["norm"], eps)
            mixed = _conv(c, layer["conv"], xn, pos)
            squares.append(jnp.stack([_squares(mixed, first), _squares(xn, first)]))
            x = x + mixed
        else:
            x = x + _attention(c, layer["gqa"], _norm(x, layer["gqa"]["norm"], eps), doc, pos)
        xn = _norm(x, layer["mlp_norm"], eps)
        if "mlp" in layer:
            x = x + _swiglu(xn, layer["mlp"])
            continue
        score = jax.nn.sigmoid(xn @ layer["router"])
        _, idx = jax.lax.top_k(score + layer["router_bias"], c["num_experts_per_tok"])
        chosen = jnp.take_along_axis(score, idx, axis=-1)
        weight = c["routed_scaling_factor"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)

        def add_expert(e, y, xn=xn, idx=idx, weight=weight, experts=layer["experts"]):
            mine = jnp.sum(jnp.where(idx == lo + e, weight, 0), axis=-1)  # 0 where e was not chosen
            return y + mine[:, None] * _swiglu(xn, jax.tree.map(lambda v: v[e], experts))

        # every held expert on every token, weighted by 0 where it was not chosen
        x = x + jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(xn))
        held.append(jnp.sum((idx >= lo) & (idx < hi)))
    logits = (_norm(x, w["final_norm"], eps) @ w["head"]).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.roll(ids, -1)[:, None], axis=-1)[:, 0]
    counted = (at + 1 < t) & (jnp.roll(doc, -1) == doc)
    loss = jnp.sum(jnp.where(counted, nll, 0.0)) / jnp.maximum(jnp.sum(counted), 1)
    return (loss, logits[t - n_probe:], jnp.stack(held) if held else jnp.zeros((0,), jnp.int32),
            jnp.stack(squares) if squares else jnp.zeros((0, 2, 2), jnp.float32))


def _zscore(x):
    centred = x - jnp.mean(x)
    return centred / jnp.maximum(jnp.sqrt(jnp.mean(centred * centred)), 1e-12)


@functools.lru_cache(maxsize=4)
def _programs(config_json: str, pop: int, n_probe: int, precision: str) -> tuple:
    """The jitted member, tell and distance of a configuration, kept so that
    one process following several seeds compiles them once."""
    config = json.loads(config_json)
    dtype = jnp.dtype(precision)
    rank, sigma, lr = int(config["rank"]), float(config["noise_stdev"]), float(config["learning_rate"])
    pairs, scale = pop // 2, sigma / math.sqrt(rank)
    shapes, treedef = jax.tree.flatten(_shapes(config), is_leaf=_is_shape)

    def dense(leaf, fac, sign):
        if fac is None:
            return leaf
        a, b = (v.astype(dtype) for v in fac)
        return leaf + (sign * scale) * jnp.einsum("...ir,...or->...io", a, b)

    @jax.jit
    def member(centre, k, p, sign, ids, doc, pos):
        facs = [_factors(k, l, s, p, rank) for l, s in enumerate(shapes)]
        w = jax.tree.unflatten(treedef, [dense(v, f, sign) for v, f in zip(centre, facs)])
        return _forward(config, w, ids, doc, pos, n_probe)

    @jax.jit
    def tell(centre, k, fitness):
        diff = (fitness[:pairs] - fitness[pairs:]).astype(dtype)
        out = []
        for l, (leaf, s) in enumerate(zip(centre, shapes)):
            if len(s) not in (2, 3):
                out.append(leaf)
                continue
            a, b = (
                v.astype(dtype)
                for v in jax.vmap(lambda p: _factors(k, l, s, p, rank))(jnp.arange(pairs))
            )
            grad = jnp.einsum("p,p...ir,p...or->...io", diff * scale, a, b)
            out.append(leaf - lr * (grad / (pop * sigma)))
        return out

    @jax.jit
    def distance(a, b):
        return sum(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2) for x, y in zip(a, b))

    return member, tell, distance


def follow(config: dict, traffic: dict, seed: int, generations: list, precision: str = "float32",
           program: list = ()) -> list:
    """From the seed through the generations asked for, one snapshot each.
    ``precision="bfloat16"`` is the control: the centre and all arithmetic in
    bfloat16.

    ``program``: the snapshots under comparison, one for each of
    ``generations``. A generation starts from the centre of the program's
    snapshot of the generation before, where there is one; its tell is then
    applied to **the program's own fitness** of that generation, and the
    snapshot holds how far the program's centre lies from the result
    (``center_diff``) and how long the step was (``center_step``) in place of
    the centre itself (3.3 GB at the cell's size). Without ``program`` the
    snapshots are a trajectory of their own, each with its ``center``."""
    dtype = jnp.dtype(precision)
    pop, pairs = int(traffic["pop"]), int(traffic["pop"]) // 2
    n_probe = min(int(config["probe_positions"]), int(traffic["seq_len"]))
    member, tell, distance = _programs(json.dumps(config, sort_keys=True), pop, n_probe, precision)
    key = _key(seed)
    k_algo, k_prob = jax.random.split(key)
    akey, _ = jax.random.split(k_algo)
    as_centre = lambda tree: [jnp.asarray(v, dtype) for v in jax.tree.leaves(tree)]

    centre = as_centre(_init(config, jax.random.fold_in(key, 1)))
    given = {int(s["generation"]): s for s in program}
    claimed = dict(zip((int(g) for g in generations), program))
    snaps, uploaded = [], None  # uploaded: (generation, the program's centre of it, on the device)
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        for step in range(1, max(generations, default=0) + 1):
            if step - 1 in given and "center" in given[step - 1]:
                centre = uploaded[1] if uploaded and uploaded[0] == step - 1 else as_centre(
                    given[step - 1]["center"])
            uploaded = None
            akey, k = jax.random.split(akey)
            ids, doc, pos = _batch(k_prob, step - 1, config, traffic)
            losses, probe, held, squares = [], [], 0, 0.0
            for sign in (1.0, -1.0):
                for p in range(pairs):
                    loss, logits, n_held, square = member(centre, k, p, sign, ids, doc, pos)
                    losses.append(loss)
                    held = held + n_held
                    squares = squares + square
                    if p == 0:
                        probe.append(np.asarray(logits))
            fitness = _zscore(jnp.stack(losses).astype(dtype))
            squares = np.asarray(squares, np.float64).reshape(-1, 2, 2)  # layer, (output, input), (all, first)
            snap = {
                "generation": step,
                "losses": np.asarray(jnp.stack(losses), np.float32),
                "fitness": np.asarray(fitness, np.float32),
                "probe": np.stack(probe),
                "held": np.asarray(held),
                "conv_gain": np.sqrt(squares[:, 0] / squares[:, 1]).astype(np.float32),  # (layers, 2)
            }
            theirs = claimed.get(step)
            used = fitness if theirs is None else jnp.asarray(theirs["fitness"], dtype)
            new = tell(centre, k, used)
            if theirs is None:
                snap["center"] = [np.asarray(v) for v in new]
            else:
                got = as_centre(theirs["center"])
                snap["center_diff"] = math.sqrt(float(distance(got, new)))
                snap["center_step"] = math.sqrt(float(distance(centre, new)))
                if given.get(step) is theirs:
                    uploaded = (step, got)
                del got
            centre = new
            if step in generations:
                snaps.append(snap)
    return snaps


def numbers(config: dict, program: list, reference: list) -> dict:
    """The numbers compared, for each step followed (``reference`` is
    ``follow(..., program=program)``):

    - ``loss_err``: the largest difference of a member's shaped fitness (its
      loss, z-scored over the population: in units of the losses' spread)
      from the reference's;
    - ``logit_err``: over the probe's positions (members 0 and ``pop / 2``,
      the row's last positions) the median of ``|logits - reference's| /
      |reference's|`` (a token whose fourth and fifth expert swap on rounding
      moves its own logits only: the median does not see it);
    - ``routing_off``: the largest difference, over the expert layers, of
      the count of assignments that landed on held experts, over the square
      root of the reference's count (flips of a token's last choice are a
      random walk: the number holds its size from the tests' tiny cut to the
      cell's);
    - ``center_err``: the distance of the program's centre from the
      reference's tell applied to the program's own fitness, over the length
      of that step (1.0: the centre did not move);
    - ``generation_off``: whether the generation counter counts the steps;
    - ``conv_gain_err``: the largest difference, over the convolution layers
      and the two gains of each, of the root mean square of the mixer's output
      over that of its normed input from the reference's, over the
      reference's: over members, every token and channels, and over the first
      ``taps - 1`` tokens of the documents alone, where a convolution that does
      not stop at a document's start reads up to three times the energy.
    """
    out = {}
    for k, (got, want) in enumerate(zip(program, reference), 1):
        f_got, f_want = (np.asarray(s["fitness"], np.float64) for s in (got, want))
        out[f"step{k}_loss_err"] = float(np.max(np.abs(f_got - f_want)))
        p_got, p_want = (np.asarray(s["probe"], np.float64) for s in (got, want))
        rel = np.linalg.norm(p_got - p_want, axis=-1) / np.maximum(np.linalg.norm(p_want, axis=-1), 1e-30)
        out[f"step{k}_logit_err"] = float(np.median(rel))
        h_got, h_want = (np.asarray(s["held"], np.float64) for s in (got, want))
        out[f"step{k}_routing_off"] = float(np.max(np.abs(h_got - h_want) / np.sqrt(np.maximum(h_want, 1.0))))
        out[f"step{k}_center_err"] = float(want["center_diff"] / max(want["center_step"], 1e-30))
        out[f"step{k}_generation_off"] = float(abs(int(got["generation"]) - int(want["generation"])))
        g_got, g_want = (np.asarray(s["conv_gain"], np.float64) for s in (got, want))
        out[f"step{k}_conv_gain_err"] = float(np.max(np.abs(g_got - g_want) / g_want))
    return out
