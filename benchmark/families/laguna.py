"""The ``laguna`` family (Hugging Face ``poolside/Laguna-S-2.1``,
``model_type: laguna``): grouped-query attention whose head count, window and
rotary positions are properties of the LAYER (full layers: 48 query heads,
YaRN on half of each head; window layers: 72 query heads over the last 512
positions, plain rotary), a sigmoid gate a head on the attention's output,
then a gated MLP (layer 0) or routed experts with one shared expert. The one
place in the benchmark that knows this model: its sizes under their published
keys, its weights from the seed, its plain reference in ``jax.numpy`` and
float32 under ``highest`` (every key at once under a mask, no cache, no
kernel, no batching, queries a block at a time; experts by a plain pass over
the held experts), the hand-over of those weights to the program's own model,
and the operations and bytes of a decode step. Independent of the program's
block: nothing of ``distkeras_tpu`` is imported but the zoo entry that
``build_program_model`` hands the weights to. What no model owns of a
routed-expert family's reference (RMSNorm, the gated MLP, the embedding, the
final norm, the head, how a request's gaps are judged) is taken from
``families/deepseek_v3.py``.

The layer equations, for layer ``l``, ``x`` ``(T, d)``, no bias anywhere
(each departure from the published model is listed in the configuration file
under ``assumed``)::

    h = RMSNorm(x);  H = num_attention_heads_per_layer[l]
    q = h Wq (T, H, Dh);  k = h Wk, v = h Wv (T, Hkv, Dh);  g = sigmoid(h Wg) (T, H)
    q, k rotated: pairs (2i, 2i+1) of the first ``partial x Dh`` values of a
        head turned by pos * f_i, the rest as they are. Window layers: f_i =
        theta^(-2i/n). Full layers (YaRN): f_i blended between that and f_i /
        factor by the linear ramp between the correction dimensions of
        beta_fast and beta_slow, cosine and sine times attention_factor
    a[t, j] = softmax_s(q[t, j] . k[s, j // (H / Hkv)] / sqrt(Dh)) v[s, ..],
        s <= t and, in a window layer, s > t - window
    x = x + concat_j(g[t, j] a[t, j]) Wo
    u = RMSNorm(x)
    dense layer:  x = x + (silu(u Wg') * (u Wu)) Wd
    expert layer: p = softmax(u Wr) over all experts; S = the top_k largest;
        w_e = routed_scale * p_e / sum_S p;  x = x + sum_{e in S, held}
        w_e E_e(u) + E_shared(u)
    last: RMSNorm, head (d, V), untied, no bias; no position table.

Weights are made bfloat16 and the reference upcasts them a layer (an expert)
at a time. The tree is what ``zoo.laguna_lm`` holds::

    {"0": {"tokens": (V, d)},
     "1".."L": {"ln1": {gamma},
                "attn": {wq (d, H Dh), wk, wv (d, Hkv Dh), wo (H Dh, d),
                         wgate (d, H)},
                "ln2": {gamma},
                "ffn": {wg, wu, wd}                      (dense layers)
                     | {"router": {wr (d, E)},
                        "experts": {wg, wu (E_held, d, m), wd (E_held, m, d)},
                        "shared": {wg, wu, wd}}},
     "L+1": {gamma}, "L+2": {"kernel": (d, V)}}
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import deepseek_v3 as latent
from benchmark.families.deepseek_v3 import (  # noqa: F401  (the contract's)
    embed, f32, final_norm, gated, judged, logits, rms_norm)
from benchmark.reference import (
    adam_step, dot_highest, get_dot, leaf_norms, leaf_norms_of_difference)

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------- sizes and weights


def _frozen(x):
    """Lists and dicts as tuples, so that a width dict keys a cache."""
    if isinstance(x, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_frozen(v) for v in x)
    return x


def widths(config: dict) -> dict:
    """The sizes of a configuration file under the names used here.
    ``num_experts`` counts the routed experts this chip holds of each
    expert layer (ids ``0 .. experts_held - 1``) where the file lists it
    under ``reduced``; the router keeps the source's ``experts`` outputs.
    The per-layer lists are the held layers'. ``swap_*``: as the
    ``deepseek_v3`` family has them."""
    a = config["assumed"]
    check = config.get("serving", {}).get("check", {})
    held = int(config["num_experts"])
    layers = int(config["num_hidden_layers"])
    lists = {name: tuple(config[name]) for name in (
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types",
        "gating_types")}
    if {len(v) for v in lists.values()} != {layers}:
        raise ValueError(
            f"the per-layer lists do not hold num_hidden_layers = {layers} "
            f"entries each")
    return {
        **({"swap_share": float(check["swap_share"]),
            "swap_floor": int(check["swap_floor"]),
            "swap_gap_limit": float(check["swap_gap_limit"]),
            "gap_limit": float(check["gap_limit"])}
           if "swap_share" in check else {}),
        "vocab": int(config["vocab_size"]),
        "seq": int(config["max_position_embeddings"]),
        "layers": layers,
        "d": int(config["hidden_size"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "heads": lists["num_attention_heads_per_layer"],
        "layer_types": lists["layer_types"],
        "mlp_types": lists["mlp_layer_types"],
        "gating": lists["gating_types"],
        "window": int(config["sliding_window"]),
        "rope": _frozen(config["rope_parameters"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": int(config["shared_expert_intermediate_size"]),
        "experts": int(config.get("reduced_from", {}).get(
            "num_experts", [held])[0]),
        "experts_held": held,
        "top_k": int(config["num_experts_per_tok"]),
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "eps": float(config["rms_norm_eps"]),
        "init": float(a["initializer_range"]),
        # tokens a page of the served pool (the kernel copies whole pages)
        "page": int(config.get("serving", {}).get("page_size", 16)),
    }


def _attention_params(w: dict, heads: int) -> int:
    d, hd = w["d"], w["head_dim"]
    return (2 * d * heads * hd + 2 * d * w["kv_heads"] * hd + d * heads)


def param_count(w: dict) -> dict:
    d, v = w["d"], w["vocab"]
    expert = 3 * d * w["expert_width"]
    shared = 3 * d * w["shared_width"]
    dense = 3 * d * w["dense_width"]
    router = d * w["experts"]
    attention = [_attention_params(w, h) for h in w["heads"]]
    ffn = [dense if kind == "dense" else
           router + shared + w["experts_held"] * expert
           for kind in w["mlp_types"]]
    return {
        "attention_full": _attention_params(w, min(w["heads"])),
        "attention_window": _attention_params(w, max(w["heads"])),
        "attention": sum(attention), "dense_mlp": dense, "expert": expert,
        "shared": shared, "router": router, "embedding": v * d, "head": d * v,
        "total": sum(attention) + sum(ffn) + w["layers"] * 2 * d
        + 2 * v * d + d,
    }


_SHAPE_KEYS = ("vocab", "layers", "d", "kv_heads", "head_dim", "heads",
               "mlp_types", "dense_width", "expert_width", "shared_width",
               "experts", "experts_held", "init")


def _zoo_entry():
    """The program's entry for this model; a program that has none cannot
    run the configuration, and says so before anything is computed."""
    from distkeras_tpu.models import zoo

    entry = getattr(zoo, "laguna_lm", None)
    if entry is None:
        raise RuntimeError(
            "the program has no zoo.laguna_lm: it cannot run a "
            "configuration of the laguna family")
    return entry


def make_weights(w: dict, seed):
    """Every weight from ``seed`` in one jitted call, on the default device,
    bfloat16: N(0, init), the output projections (wo, every wd) scaled by
    1/sqrt(2 L) as the other families do, RMSNorm gains 1."""
    _zoo_entry()
    return _make_weights(jnp.uint32(int(seed) % (2**32)),
                         **{k: w[k] for k in _SHAPE_KEYS})


@functools.partial(jax.jit, static_argnames=_SHAPE_KEYS)
def _make_weights(seed, *, vocab, layers, d, kv_heads, head_dim, heads,
                  mlp_types, dense_width, expert_width, shared_width, experts,
                  experts_held, init):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4 + 16 * layers))
    bf = jnp.bfloat16

    def normal(shape, scale=init):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(bf)

    def gain(n):
        return {"gamma": jnp.ones((n,), bf)}

    out = init / math.sqrt(2 * layers)

    def mlp(width, lead=()):
        return {"wg": normal((*lead, d, width)), "wu": normal((*lead, d, width)),
                "wd": normal((*lead, width, d), out)}

    params = {"0": {"tokens": normal((vocab, d))}}
    for i in range(layers):
        if mlp_types[i] == "dense":
            ffn = mlp(dense_width)
        else:
            ffn = {"router": {"wr": normal((d, experts))},
                   "experts": mlp(expert_width, (experts_held,)),
                   "shared": mlp(shared_width)}
        h = heads[i]
        params[str(i + 1)] = {
            "ln1": gain(d),
            "attn": {"wq": normal((d, h * head_dim)),
                     "wk": normal((d, kv_heads * head_dim)),
                     "wv": normal((d, kv_heads * head_dim)),
                     "wo": normal((h * head_dim, d), out),
                     "wgate": normal((d, h))},
            "ln2": gain(d),
            "ffn": ffn,
        }
    params[str(layers + 1)] = gain(d)
    params[str(layers + 2)] = {"kernel": normal((d, vocab))}
    return params


# ------------------------------------------------------------ hand-over


def _thaw(x):
    """``_frozen``'s tuples of pairs as dicts again."""
    if isinstance(x, tuple) and x and all(
            isinstance(p, tuple) and len(p) == 2 and isinstance(p[0], str)
            for p in x):
        return {k: _thaw(v) for k, v in x}
    return x


def build_program_model(w: dict, weights, traffic: dict):
    """The program's own model with the benchmark's seeded weights in it:
    ``zoo.laguna_lm`` built under ``jax.eval_shape`` from the configuration's
    own keys, its tree checked leaf by leaf against the layout above, the
    arrays of ``make_weights`` in its place (bfloat16 where the program
    initialises float32)."""
    entry = _zoo_entry()
    holder = []

    def build():
        model = entry(
            vocab_size=w["vocab"], seq_len=w["seq"], hidden_size=w["d"],
            num_key_value_heads=w["kv_heads"], head_dim=w["head_dim"],
            intermediate_size=w["dense_width"],
            moe_intermediate_size=w["expert_width"],
            shared_expert_intermediate_size=w["shared_width"],
            num_experts=w["experts"], num_experts_per_tok=w["top_k"],
            layer_types=w["layer_types"],
            num_attention_heads_per_layer=w["heads"],
            mlp_layer_types=w["mlp_types"], gating_types=w["gating"],
            sliding_window=w["window"], rope_parameters=_thaw(w["rope"]),
            moe_routed_scaling_factor=w["routed_scale"],
            norm_topk_prob=w["norm_topk"], rms_norm_eps=w["eps"],
            experts_held=(None if w["experts_held"] == w["experts"]
                          else list(range(w["experts_held"]))),
            seed=0)
        holder.append(model)
        return model.params

    want = jax.eval_shape(build)
    model = holder[0]
    if jax.tree.structure(want) != jax.tree.structure(weights) or any(
            a.shape != b.shape
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(weights))):
        raise RuntimeError(
            "the program's laguna_lm no longer has the tree that "
            "benchmark/families/laguna.py documents: the hand-over format "
            "moved")
    model.params = weights
    return model


# -------------------------------------------------------------- forward


ROW_BLOCK_ATTN = 256  # query rows whose scores are held at once (72 heads)


def pair_frequencies(kind_rope: dict, n: int) -> tuple:
    """``(f (n / 2,), factor)``: what pair ``i`` of a head's ``n`` rotary
    values turns by a position, and what multiplies cosine and sine.
    ``default``: ``theta^(-2i/n)`` and 1. ``yarn``: with ``c(b) = n ln(orig /
    (2 pi b)) / (2 ln theta)`` the dimension that turns ``b`` times over the
    original positions, ``lo = floor(c(beta_fast))``, ``hi =
    ceil(c(beta_slow))`` (kept inside ``[0, n - 1]``), ``r_i = clip((i - lo)
    / (hi - lo), 0, 1)``: ``f_i (1 - r_i) + (f_i / factor) r_i``, and the
    configuration's ``attention_factor``."""
    theta = float(kind_rope["rope_theta"])
    f = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    if kind_rope.get("rope_type", "default") == "default":
        return f, 1.0
    orig = float(kind_rope["original_max_position_embeddings"])

    def c(b):
        return n * math.log(orig / (2 * math.pi * b)) / (2 * math.log(theta))

    lo = max(math.floor(c(float(kind_rope["beta_fast"]))), 0)
    hi = min(math.ceil(c(float(kind_rope["beta_slow"]))), n - 1)
    r = np.clip((np.arange(n // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f * (1 - r) + f / float(kind_rope["factor"]) * r,
            float(kind_rope.get("attention_factor", 1.0)))


def rotate(x, pos, kind_rope: dict):
    """``x`` ``(T, heads, Dh)`` at ``pos`` ``(T,)``: the first ``partial x
    Dh`` values of every head turned in pairs ``(2i, 2i+1)``."""
    hd = x.shape[-1]
    n = int(round(hd * float(kind_rope.get("partial_rotary_factor", 1))))
    f, factor = pair_frequencies(kind_rope, n)
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(f, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pairs = x[..., :n].reshape(x.shape[:-1] + (n // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return jnp.concatenate(
        [turned.reshape(x.shape[:-1] + (n,)), x[..., n:]], axis=-1)


def attention(p, x, w: dict, dot, heads: int, kind: str):
    """Grouped-query attention of one sequence with the gate a head; ``x``
    is (T, d), normalised; ``kind`` the layer's ``layer_types`` entry."""
    t = x.shape[0]
    kvh, hd = w["kv_heads"], w["head_dim"]
    g = heads // kvh
    window = w["window"] if kind == "sliding_attention" else None
    kind_rope = dict(_thaw(w["rope"])[kind])
    p = f32(p)
    pos = jnp.arange(t)
    q = rotate(dot(x, p["wq"]).reshape(t, heads, hd), pos, kind_rope)
    k = rotate(dot(x, p["wk"]).reshape(t, kvh, hd), pos, kind_rope)
    v = dot(x, p["wv"]).reshape(t, kvh, hd)
    gate = jax.nn.sigmoid(dot(x, p["wgate"]))  # (T, H)

    def rows(args):
        qb, at = args
        qg = qb.reshape(qb.shape[0], kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) \
            / math.sqrt(hd)
        see = pos[None, :] <= at[:, None]
        if window is not None:
            see = see & (pos[None, :] > at[:, None] - window)
        s = jnp.where(see[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", a, v, precision=HIGHEST)
        return o.reshape(qb.shape[0], heads, hd)

    if t > ROW_BLOCK_ATTN and t % ROW_BLOCK_ATTN == 0:
        nb = t // ROW_BLOCK_ATTN
        o = jax.lax.map(rows, (q.reshape(nb, ROW_BLOCK_ATTN, heads, hd),
                               pos.reshape(nb, ROW_BLOCK_ATTN)))
        o = o.reshape(t, heads, hd)
    else:
        o = rows((q, pos))
    return dot((o * gate[:, :, None]).reshape(t, heads * hd), p["wo"])


def route(p, x, w: dict, dot):
    """Softmax scores over all routed experts; the ``top_k`` largest;
    weights = the chosen scores over their sum (``norm_topk``), times the
    scale. Also the margin between the last expert taken and the first left
    out, in score. ``x`` is (T, d)."""
    k = w["top_k"]
    s = jax.nn.softmax(dot(x, f32(p)["wr"]), axis=-1)
    order = jnp.argsort(-s, axis=-1)[:, :k + 1]
    chosen = order[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if w["norm_topk"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    edge = jnp.take_along_axis(s, order[:, k - 1:], axis=-1)
    return chosen, picked * w["routed_scale"], edge[:, 0] - edge[:, 1]


def expert_layer(p, x, w: dict, dot, held=None, with_shared=True):
    """The routed experts over all tokens, a plain pass over the experts one
    after another, plus the shared expert; ``x`` is (T, d). The stacked
    weights are those of experts ``0 .. experts_held - 1``; ``held`` (ids)
    takes a share of that stack, for the test that adds the shares up.
    Returns the output and ``route``'s margin a token."""
    n, e = x.shape[0], w["experts"]
    held = np.arange(w["experts_held"]) if held is None else np.asarray(held)
    chosen, weight, margin = route(p["router"], x, w, dot)
    gate = jnp.zeros((n, e), jnp.float32).at[
        jnp.arange(n)[:, None], chosen].set(weight)

    def one(acc, ex):
        wg, wu, wd, g = ex
        y = gated({"wg": wg.astype(jnp.float32), "wu": wu.astype(jnp.float32),
                   "wd": wd.astype(jnp.float32)}, x, dot)
        return acc + g[:, None] * y, None

    ex = p["experts"]
    if len(held) != ex["wg"].shape[0]:
        ex = {name: stack[held] for name, stack in ex.items()}
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (ex["wg"], ex["wu"], ex["wd"], gate.T[held]))
    if with_shared:
        out = out + gated(f32(p["shared"]), x, dot)
    return out, margin


def layer(p, x, w: dict, dot, index: int):
    """Layer ``index`` (from 0) over one sequence, (T, d) float32, and the
    expert layer's routing margin a token (infinite for the dense layer)."""
    g1 = p["ln1"]["gamma"].astype(jnp.float32)
    x = x + attention(p["attn"], rms_norm(x, g1, w["eps"]), w, dot,
                      w["heads"][index], w["layer_types"][index])
    u = rms_norm(x, p["ln2"]["gamma"].astype(jnp.float32), w["eps"])
    if w["mlp_types"][index] == "dense":
        return x + gated(f32(p["ffn"]), u, dot), jnp.full(
            x.shape[:1], jnp.inf, jnp.float32)
    y, margin = expert_layer(p["ffn"], u, w, dot)
    return x + y, margin


def hidden(params, tokens, w: dict, dot=dot_highest):
    """The final RMSNorm's output for one sequence of token ids: (T, d)."""
    x = embed(params, tokens)
    for i in range(w["layers"]):
        x, _ = layer(params[str(i + 1)], x, w, dot, i)
    return final_norm(params, x, w)


def _key(w: dict) -> tuple:
    return tuple(sorted((k, _frozen(v)) for k, v in w.items()))


# ------------------------------------------------------------- training


def sequence_loss(params, tokens, w: dict, dot=dot_highest):
    """Mean next-token cross-entropy of one sequence (T - 1 predictions)."""
    h = hidden(params, tokens, w, dot)
    logp = jax.nn.log_softmax(logits(params, h[:-1], w, dot), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def _row_grad_fn(w_items: tuple, precision: str):
    w = dict(w_items)
    dot = get_dot(precision)
    return jax.jit(jax.value_and_grad(
        lambda p, row: sequence_loss(p, row, w, dot)))


def train_readings(w: dict, seed, batches, lr: float,
                   precision: str = "highest", moment_after: int = 1) -> dict:
    """What a training check compares, as the ``deepseek_v3`` family gives
    it, over a float32 copy of the seeded weights and a row at a time. For
    the tiny size of the tests: no cell trains this family (16 bytes a
    parameter of float32 state fit no chip at the serving cell's size)."""
    fn = _row_grad_fn(_key(w), precision)
    start = f32(make_weights(w, seed))
    params = jax.tree.map(jnp.copy, start)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    losses, moment_norms = [], None
    for i, batch in enumerate(batches):
        rows = [fn(params, jnp.asarray(row, jnp.int32))
                for row in np.asarray(batch)]
        losses.append(float(sum(l for l, _ in rows)) / len(rows))
        grads = jax.tree.map(lambda *g: sum(g) / len(g), *(g for _, g in rows))
        params, mu, nu, count = adam_step(params, grads, mu, nu, count, lr=lr)
        if i + 1 == moment_after:
            moment_norms = np.asarray(leaf_norms(mu))
    return {"losses": losses, "moment_norms": moment_norms,
            "change_norms": np.asarray(leaf_norms_of_difference(params, start))}


# -------------------------------------------------------------- serving


@functools.lru_cache(maxsize=None)
def _layer_fn(w_items: tuple, precision: str, index: int):
    """One layer as a program of its own: the forward is called a layer at a
    time, so that one layer's weights (one expert's, inside the expert
    layer's pass) are held upcast beside the bfloat16 tree and no more."""
    w = dict(w_items)
    return jax.jit(lambda p, x: layer(p, x, w, get_dot(precision), index))


def _residual(params, w: dict, seq, precision: str):
    """The last layer's output, a layer a call, and a position's narrowest
    routing margin over the expert layers (``route``)."""
    key = _key(w)
    x = jax.jit(embed)(params, seq)
    narrowest = jnp.full(seq.shape, jnp.inf, jnp.float32)
    for i in range(w["layers"]):
        x, margin = _layer_fn(key, precision, i)(params[str(i + 1)], x)
        narrowest = jnp.minimum(narrowest, margin)
    return x, narrowest


def served_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """As the ``deepseek_v3`` family's: for one finished request, one full
    forward of the reference; at each served position how far the served
    token's logit lies below the reference's largest, the same for a
    ``control`` precision's first token, and the position's narrowest
    routing margin in the reference."""
    key = _key(w)
    n = len(sequence)
    bucket = latent.SEQ_BUCKET
    while bucket < n:
        bucket *= 2
    padded = np.zeros(min(w["seq"], bucket), np.int32)
    padded[:n] = sequence  # causal: what follows a position cannot reach it
    seq = jnp.asarray(padded)
    ref_x, narrowest = _residual(params, w, seq, "highest")
    low_x = _residual(params, w, seq, control)[0] if control else None
    served = np.asarray(sequence[prompt_len:], np.int64)
    positions = np.arange(prompt_len - 1, n - 1)
    block = latent.ROW_BLOCK
    gaps, control_gaps = [], []
    for i in range(0, len(positions), block):
        pos = positions[i:i + block]
        rows = np.zeros(block, np.int32)
        rows[:len(pos)] = pos
        ref = np.asarray(latent._logits_fn(key, "highest")(params, ref_x, rows))[:len(pos)]
        best = ref.max(axis=-1)
        at = np.arange(len(pos))
        gaps.append(best - ref[at, served[i:i + block]])
        if control:
            low = np.asarray(latent._logits_fn(key, control)(params, low_x, rows))
            control_gaps.append(best - ref[at, low[:len(pos)].argmax(axis=-1)])
    return (np.concatenate(gaps),
            np.concatenate(control_gaps) if control else None,
            np.asarray(narrowest)[positions])


def token_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """``served_gaps`` as ``judged``: what the serving check takes the
    widest of."""
    gaps, control_gaps, _ = served_gaps(params, w, sequence, prompt_len, control)
    return judged(gaps, w), control_gaps


# --------------------------------------------------- operations and bytes
#
# Counted from the algorithm, never from the compiler's cost analysis
# (``flops.py`` says how).


def _cached_in_reach(w: dict, kind: str, cached: float) -> float:
    """Of ``cached`` tokens, those a layer of ``kind`` reads."""
    return min(cached, w["window"]) if kind == "sliding_attention" else cached


def decode_step(w: dict, batch: float, cached: float, *, weight_bytes: float,
                kv_bytes: float) -> dict:
    """One decode step for ``batch`` active sequences with ``cached`` tokens
    each in the cache (means over the window). Every matrix but the routed
    experts' is read once and used for ``batch`` tokens. Of an expert layer's
    held experts, those that some token of the batch reaches are read:
    ``E_held x (1 - (1 - k / E)^batch)`` under EVEN routing (every expert
    equally likely for every token, tokens independent), which is what seeded
    random weights give and a trained router only approximates; of a token's
    ``k`` picks ``E_held / E`` reach a held expert, at 2 operations a
    parameter. A cached token is ``2 x Hkv x Dh`` values a layer, read once
    by every layer that has it in reach: all of a full layer's, the last
    ``window`` of a window layer's; a token in reach costs a layer's ``H``
    query heads ``Dh x 2`` operations for its score and as many for the
    weighted sum. The embedding is a lookup.

    ``parts`` gives the same count by part: ``attn`` (every layer's five
    matrices and its cache in reach), ``moe`` (router, routed experts
    reached, shared expert), ``dense`` (the dense layers' MLP) and ``head``;
    they sum to the whole. ``kernel``: the paged kernel's calls alone
    (``paged_attention_step``), a part of ``attn`` counted by whole pages."""
    d, hd, kvh = w["d"], w["head_dim"], w["kv_heads"]
    attn = {"flops": 0.0, "bytes": 0.0}
    cache = 0.0
    for heads, kind in zip(w["heads"], w["layer_types"]):
        reach = _cached_in_reach(w, kind, cached)
        params = _attention_params(w, heads)
        mine = 2 * kvh * hd * reach * batch * kv_bytes
        cache += mine
        attn["flops"] += 2 * batch * params + batch * reach * heads * hd * 4
        attn["bytes"] += params * weight_bytes + mine
    n_moe = sum(kind == "sparse" for kind in w["mlp_types"])
    n_dense = w["layers"] - n_moe
    expert = 3 * d * w["expert_width"]
    shared = 3 * d * w["shared_width"]
    router = d * w["experts"]
    reached = w["experts_held"] * (
        1.0 - (1.0 - w["top_k"] / w["experts"]) ** batch)
    held_share = w["experts_held"] / w["experts"]
    moe = {
        "flops": n_moe * 2 * batch * (
            router + shared + w["top_k"] * held_share * expert),
        "bytes": n_moe * (router + shared + reached * expert) * weight_bytes,
    }
    mlp = 3 * d * w["dense_width"]
    dense = {"flops": n_dense * 2 * batch * mlp,
             "bytes": n_dense * mlp * weight_bytes}
    head = {"flops": 2 * batch * d * w["vocab"],
            "bytes": d * w["vocab"] * weight_bytes}
    parts = {"attn": attn, "moe": moe, "dense": dense, "head": head}
    total_bytes = sum(p["bytes"] for p in parts.values())
    return {
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": total_bytes, "weight_bytes": total_bytes - cache,
        "kv_bytes": cache, "experts_reached_a_layer": reached,
        "parts": parts,
        "kernel": paged_attention_step(w, batch, cached, kv_bytes=kv_bytes),
    }


def paged_attention_step(w: dict, batch: float, cached: float, *,
                         kv_bytes: float) -> dict:
    """What the paged kernel's calls of one decode step have to move and
    compute, alone: for every layer, the whole pages that hold the positions
    in reach of ``batch`` slots of ``cached`` tokens (a page is copied whole:
    ``ceil(cached / page)`` pages of a full layer, of a window layer at most
    the ``window / page + 1`` that a window can straddle), keys and values of
    every K/V head; the queries in and the outputs back in float32; ``4 x H
    x Dh`` operations a position in reach."""
    hd, kvh, page_size = w["head_dim"], w["kv_heads"], w["page"]
    page = 2 * page_size * kvh * hd * kv_bytes
    out = {"flops": 0.0, "bytes": 0.0, "pages_a_slot": {}}
    for heads, kind in zip(w["heads"], w["layer_types"]):
        pages = math.ceil(cached / page_size)
        if kind == "sliding_attention":
            pages = min(pages, w["window"] // page_size + 1)
        reach = _cached_in_reach(w, kind, cached)
        out["pages_a_slot"][kind] = pages
        out["bytes"] += batch * (pages * page + 2 * heads * hd * 4)
        out["flops"] += batch * reach * heads * hd * 4
    return out
