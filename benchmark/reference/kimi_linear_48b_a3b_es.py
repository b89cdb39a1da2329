"""Plain reference of the ``kimi_linear_48b_a3b_es`` configuration.

Low-rank OpenES (mirrored pairs, z-scored fitness, plain SGD on the centre)
over a language model whose layers mix tokens by Kimi Delta Attention (KDA: a
gated delta rule with a short convolution, computed here **a token at a
time**) or by latent attention without rotation (MLA, NoPE), over a mixture of
experts (``kimi_linear``), written in straightforward ``jax.numpy`` at float32 with
``highest`` matmul precision. There is no factor form here: for every member
the dense ``W + sign * sigma / sqrt(rank) * A @ B.T`` of each matrix is
**materialised**, and the member's plain forward pass runs on those weights, a
member at a time. It imports nothing of the program and draws everything from
the seed.

What it shares with the program is the semantics, and the order in which keys
are folded and split, because the random draws are part of the semantics:

- workflow: ``k_algo, k_prob = split(key(seed))``;
- centre: the tree of ``_shapes``; leaf ``l`` of ``jax.tree.leaves``: a
  leaf of two or three axes (KDA's convolutions among them) is ``init_std *
  normal(fold_in(fold_in(key, 1), l))``, a norm gain one, the router's
  correction bias zero, KDA's ``A_log = log(uniform(1, 16))`` and ``dt_bias``
  the inverse softplus of ``exp(uniform(log 0.001, log 0.1))``, both from the
  leaf's key;
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
``num_experts_published`` experts and add only the experts ``experts_held``;
the vocabulary is the held rows; the layers are the first ``layers`` of
``linear_attn_config``'s numbering, which counts from 1.
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


def _kinds(c: dict) -> list:
    """How each layer held mixes tokens: ``linear_attn_config`` counts from 1."""
    linear = c["linear_attn_config"]
    where = {**{l: "mla" for l in linear["full_attn_layers"]}, **{l: "kda" for l in linear["kda_layers"]}}
    return [where[l] for l in range(1, c["layers"] + 1)]


def _shapes(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    held = c["experts_held"][1] - c["experts_held"][0]
    linear = c["linear_attn_config"]
    kh, kd, taps = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]

    def attn():
        return {
            "norm": (d,),
            "q": (d, h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])),
            "kva": (d, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            "kv_norm": (c["kv_lora_rank"],),
            "kvb": (c["kv_lora_rank"], h * (c["qk_nope_head_dim"] + c["v_head_dim"])),
            "o": (h * c["v_head_dim"], d),
        }

    def kda():
        wide = kh * kd
        return {
            "norm": (d,), "q": (d, wide), "k": (d, wide), "v": (d, wide),
            "q_conv": (wide, taps), "k_conv": (wide, taps), "v_conv": (wide, taps),
            "f_a": (d, kd), "f_b": (kd, wide), "A_log": (kh,), "dt_bias": (wide,),
            "beta": (d, kh), "g_a": (d, kd), "g_b": (kd, wide), "o_norm": (kd,), "o": (wide, d),
        }

    def mlp(width, stack=()):
        return {"gate": stack + (d, width), "up": stack + (d, width), "down": stack + (width, d)}

    layers = []
    for l, kind in enumerate(_kinds(c)):
        layer = {"mlp_norm": (d,), **({"kda": kda()} if kind == "kda" else {"attn": attn()})}
        if l < c["first_k_dense_replace"]:
            layer["mlp"] = mlp(c["intermediate_size"])
        else:
            layer["router"] = (d, c["num_experts_published"])
            layer["router_bias"] = (c["num_experts_published"],)
            layer["shared"] = mlp(c["num_shared_experts"] * c["moe_intermediate_size"])
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
        k = jax.random.fold_in(key, l)
        if len(shape) >= 2:
            leaves.append(c["init_std"] * jax.random.normal(k, shape))
        elif path[-1].key == "router_bias":
            leaves.append(jnp.zeros(shape))
        elif path[-1].key == "A_log":
            leaves.append(jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0)))
        elif path[-1].key == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, minval=math.log(0.001), maxval=math.log(0.1)))
            leaves.append(dt + jnp.log(-jnp.expm1(-dt)))
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


def _kda(c: dict, a: dict, xn, doc, pos):
    """One member's KDA mixer on the normed ``xn`` ``(T, hidden)``: the
    output before the residual, and the mean of ``exp(g)``. The short
    convolutions (a tap before the document's start reads zero), SiLU, the L2
    norms, the decay, beta, the delta rule a token at a time with the state
    zero at each document's first token, the output norm and gate, ``Wo``."""
    linear = c["linear_attn_config"]
    h, dk, taps = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
    t, dtype = xn.shape[0], xn.dtype

    def stream(name):
        u, w = xn @ a[name], a[name + "_conv"]  # (T, channels), (channels, taps)
        y = jnp.zeros_like(u)
        for j in range(taps):
            back = taps - 1 - j
            past = jnp.where((pos >= back)[:, None], jnp.roll(u, back, axis=0), 0)
            y = y + past * w[:, j]
        return jax.nn.silu(y).reshape(t, h, dk)

    q, k, v = stream("q"), stream("k"), stream("v")
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    g = -jnp.exp(a["A_log"])[:, None] * jax.nn.softplus(((xn @ a["f_a"]) @ a["f_b"] + a["dt_bias"]).reshape(t, h, dk))
    beta = jax.nn.sigmoid(xn @ a["beta"])  # (T, heads)

    def token(state, xs):  # state: (heads, keys, values)
        qt, kt, vt, gt, bt, first = xs
        state = jnp.where(first, 0, state) * jnp.exp(gt)[:, :, None]
        seen = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + (bt[:, None] * kt)[:, :, None] * (vt - seen)[:, None, :]
        return state.astype(dtype), jnp.einsum("hkv,hk->hv", state, qt).astype(dtype)

    _, o = jax.lax.scan(token, jnp.zeros((h, dk, dk), dtype), (q, k, v, g.astype(dtype), beta, pos == 0))
    o = _norm(o, a["o_norm"], c["rms_norm_eps"]) * jax.nn.sigmoid(((xn @ a["g_a"]) @ a["g_b"]).reshape(t, h, dk))
    return o.reshape(t, h * dk) @ a["o"], jnp.mean(jnp.exp(g.astype(jnp.float32)))


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def _forward(c: dict, w: dict, ids, doc, pos, n_probe: int):
    """One member on its own dense weights ``w``: ``(loss, the logits at the
    last n_probe positions, held assignments for each expert layer, the mean
    of exp(g) for each KDA layer)``.

    Pre-norm residual blocks, RMSNorm, final norm, untied head. A KDA layer:
    ``_kda``. An MLA layer: ``q = x Wq`` as heads of ``qk_nope + qk_rope``;
    ``x Wkva``: the first ``kv_lora_rank`` through RMSNorm give ``c``, the
    rest ``k_rope``, one for all heads; no rotation (``mla_use_nope``: the
    rope dimensions are used as they come); ``c Wkvb``: for each head
    ``k_nope`` then ``v``; scores ``q.k / sqrt(qk_nope + qk_rope)``, causal
    and within a document, softmax; heads through ``Wo``. MLPs
    ``down(silu(gate x) * up x)``. Router: ``s = sigmoid(x Wr)``, the
    ``num_experts_per_token`` largest of ``s + b``,
    weights ``routed_scaling_factor * s_e / sum of the chosen s``; the layer
    adds the shared MLP and the chosen experts held here. Loss: mean
    next-token negative log-likelihood, every position but the first of each
    document."""
    dtype = w["embed"].dtype
    eps, t = c["rms_norm_eps"], ids.shape[0]
    h, dn, dr, dv, dl = (c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                         c["v_head_dim"], c["kv_lora_rank"])
    lo, hi = c["experts_held"]
    at = jnp.arange(t)
    mask = (at[:, None] >= at[None, :]) & (doc[:, None] == doc[None, :])
    x = w["embed"][ids]
    held, kept = [], []
    for layer in w["layers"]:
        if "kda" in layer:
            mixed, retention = _kda(c, layer["kda"], _norm(x, layer["kda"]["norm"], eps), doc, pos)
            x = x + mixed
            kept.append(retention)
        else:
            a = layer["attn"]
            xn = _norm(x, a["norm"], eps)
            q = (xn @ a["q"]).reshape(t, h, dn + dr)
            kva = xn @ a["kva"]
            kv = (_norm(kva[:, :dl], a["kv_norm"], eps) @ a["kvb"]).reshape(t, h, dn + dv)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kva[:, None, dl:], (t, h, dr))], axis=-1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dn + dr)
            s = jnp.where(mask, s, jnp.finfo(dtype).min)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1).astype(dtype), kv[..., dn:])
            x = x + o.reshape(t, h * dv) @ a["o"]
        xn = _norm(x, layer["mlp_norm"], eps)
        if "mlp" in layer:
            x = x + _swiglu(xn, layer["mlp"])
            continue
        score = jax.nn.sigmoid(xn @ layer["router"])
        _, idx = jax.lax.top_k(score + layer["router_bias"], c["num_experts_per_token"])
        chosen = jnp.take_along_axis(score, idx, axis=-1)
        weight = c["routed_scaling_factor"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        def add_expert(e, y, xn=xn, idx=idx, weight=weight, experts=layer["experts"]):
            mine = jnp.sum(jnp.where(idx == lo + e, weight, 0), axis=-1)  # 0 where e was not chosen
            return y + mine[:, None] * _swiglu(xn, jax.tree.map(lambda v: v[e], experts))

        # every held expert on every token, weighted by 0 where it was not chosen
        y = jax.lax.fori_loop(0, hi - lo, add_expert, _swiglu(xn, layer["shared"]))
        held.append(jnp.sum((idx >= lo) & (idx < hi)))
        x = x + y
    logits = (_norm(x, w["final_norm"], eps) @ w["head"]).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.roll(ids, -1)[:, None], axis=-1)[:, 0]
    counted = (at + 1 < t) & (jnp.roll(doc, -1) == doc)
    loss = jnp.sum(jnp.where(counted, nll, 0.0)) / jnp.maximum(jnp.sum(counted), 1)
    return (loss, logits[t - n_probe:], jnp.stack(held) if held else jnp.zeros((0,), jnp.int32),
            jnp.stack(kept) if kept else jnp.zeros((0,), jnp.float32))


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
    the centre itself (3.4 GB at the cell's size). Without ``program`` the
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
            losses, probe, held, kept = [], [], 0, 0.0
            for sign in (1.0, -1.0):
                for p in range(pairs):
                    loss, logits, n_held, retention = member(centre, k, p, sign, ids, doc, pos)
                    losses.append(loss)
                    held = held + n_held
                    kept = kept + retention / pop
                    if p == 0:
                        probe.append(np.asarray(logits))
            fitness = _zscore(jnp.stack(losses).astype(dtype))
            snap = {
                "generation": step,
                "losses": np.asarray(jnp.stack(losses), np.float32),
                "fitness": np.asarray(fitness, np.float32),
                "probe": np.stack(probe),
                "held": np.asarray(held),
                "kda_retention": np.asarray(kept, np.float32),
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
      |reference's|`` (a token whose eighth and ninth expert swap on
      rounding moves its own logits only: the median does not see it);
    - ``routing_off``: the largest difference, over the expert layers, of
      the count of assignments that landed on held experts, over the square
      root of the reference's count (flips of a token's last choice are a
      random walk: the number holds its size from the tests' tiny cut to the
      cell's);
    - ``center_err``: the distance of the program's centre from the
      reference's tell applied to the program's own fitness, over the length
      of that step (1.0: the centre did not move);
    - ``generation_off``: whether the generation counter counts the steps;
    - ``retention_err``: the largest difference, over the KDA layers, of the
      mean of ``exp(g)`` over members, tokens, heads and channels from the
      reference's, over the reference's.
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
        r_got, r_want = (np.asarray(s["kda_retention"], np.float64) for s in (got, want))
        out[f"step{k}_retention_err"] = float(np.max(np.abs(r_got - r_want) / r_want))
    return out
