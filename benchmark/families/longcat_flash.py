"""The ``longcat_flash`` family (Hugging Face ``meituan-longcat/LongCat-Flash-
Chat``): the shortcut-connected expert layer. Two latent attentions with a
low-rank query and two dense gated MLPs a layer, and an expert layer (routed
experts beside zero-compute identity experts, softmax router) that reads the
hidden state after the first attention and is added at the layer's end. The
one place in the benchmark that knows this model: its sizes under their
published keys, its weights from the seed, its plain reference in
``jax.numpy`` and float32 under ``highest`` (expanded attention, no cache, no
kernels, experts by a plain pass over the held experts, identity picks written
as ``weight x u``), the hand-over of those weights to the program's own model,
and the operations and bytes of a decode step. Independent of the program's
block: nothing of ``distkeras_tpu`` is imported but the zoo entry that
``build_program_model`` hands the weights to. What no model owns of the
latent family's reference (RMSNorm, the rotation, the gated MLP, how a
request's gaps are judged) is taken from ``families/deepseek_v3.py``.

The layer equations (each departure from the published model is listed in the
configuration file under ``assumed``), every RMSNorm with ``eps``:

    h1 = x  + MLA_0(RMSNorm(x));   u = RMSNorm(h1);   m = MoE(u)
    h2 = h1 + MLP_0(u)
    h3 = h2 + MLA_1(RMSNorm(h2))
    h4 = h3 + MLP_1(RMSNorm(h3));  y = h4 + m
    MLP_i(z) = (silu(z Wg) * (z Wu)) Wd
    MLA_i(z): cq = RMSNorm(z Wqa);  q = sq * (cq Wqb) as H heads of [q_nope |
              q_pe];  z Wkva = [c | k_pe], one k_pe for all heads;  cn = skv *
              RMSNorm(c);  cn Wkvb as H heads of [k_nope | v];  q_pe, k_pe
              rotated by position, pairs (2i, 2i+1) turned by pos *
              theta^(-2i/rope);  scores q.k / sqrt(nope + rope), causal,
              softmax;  o = (sum w v) Wo;  sq = sqrt(d / q_rank), skv =
              sqrt(d / rank)
    MoE(u):   s = softmax(u Wr) over experts + zero outputs, float32;  chosen
              = top k of s + b;  weight_e = routed_scaling_factor * s_e, not
              normalised;  e < experts: weight_e * expert_e(u) where e is held
              here, nothing where it is not;  e >= experts: weight_e * u
    last:     RMSNorm, head (d, V), untied, no bias; no position table.

Weights are made bfloat16 and the reference upcasts them a part of a layer (an
expert) at a time. The tree is what ``zoo.longcat_flash_lm`` holds:

    {"0": {"tokens": (V, d)},
     "1".."L": {"0", "1": {"ln1": {gamma},
                           "attn": {wqa, q_norm: {gamma}, wqb, wkva,
                                    kv_norm: {gamma}, wkvb, wo},
                           "ln2": {gamma}, "mlp": {wg, wu, wd}},
                "moe": {"router": {wr (d, E + Z), bias (E + Z,)},
                        "experts": {wg, wu (E_held, d, m), wd (E_held, m, d)}}},
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
    embed, f32, final_norm, gated, judged, logits, rms_norm, rotate)
from benchmark.reference import (
    adam_step, dot_highest, get_dot, leaf_norms, leaf_norms_of_difference)

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------- sizes and weights


def widths(config: dict) -> dict:
    """The sizes of a configuration file under the names used here.
    ``n_routed_experts`` counts the routed experts this chip holds of each
    layer (ids ``0 .. experts_held - 1``) where the file lists it under
    ``reduced``; the router keeps the source's ``experts`` + ``zero``
    outputs. ``swap_*``: as the ``deepseek_v3`` family has them."""
    a = config["assumed"]
    check = config.get("serving", {}).get("check", {})
    held = int(config["n_routed_experts"])
    d, q_rank, rank = (int(config["hidden_size"]), int(config["q_lora_rank"]),
                       int(config["kv_lora_rank"]))
    return {
        **({"swap_share": float(check["swap_share"]),
            "swap_floor": int(check["swap_floor"]),
            "swap_gap_limit": float(check["swap_gap_limit"]),
            "gap_limit": float(check["gap_limit"])}
           if "swap_share" in check else {}),
        "vocab": int(config["vocab_size"]),
        "seq": int(config["max_position_embeddings"]),
        "layers": int(config["num_layers"]),
        "d": d, "n_heads": int(config["num_attention_heads"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vd": int(config["v_head_dim"]),
        "rank": rank, "q_rank": q_rank,
        "dense_width": int(config["ffn_hidden_size"]),
        "expert_width": int(config["expert_ffn_hidden_size"]),
        "experts": int(config.get("reduced_from", {}).get(
            "n_routed_experts", [held])[0]),
        "experts_held": held,
        "zero": int(config["zero_expert_num"]),
        "top_k": int(config["moe_topk"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "q_scale": math.sqrt(d / q_rank) if config["mla_scale_q_lora"] else 1.0,
        "kv_scale": math.sqrt(d / rank) if config["mla_scale_kv_lora"] else 1.0,
        "norm_topk": bool(a.get("norm_topk_prob", False)),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "init": float(a["initializer_range"]),
        "bias_init": float(a["router_bias_std"]),
    }


def _attention_params(w: dict) -> int:
    d, h = w["d"], w["n_heads"]
    return (d * w["q_rank"] + w["q_rank"] * h * (w["nope"] + w["rope"])
            + d * (w["rank"] + w["rope"])
            + w["rank"] * h * (w["nope"] + w["vd"]) + h * w["vd"] * d)


def param_count(w: dict) -> dict:
    d, v = w["d"], w["vocab"]
    attention = _attention_params(w)
    dense = 3 * d * w["dense_width"]
    expert = 3 * d * w["expert_width"]
    outputs = w["experts"] + w["zero"]
    router = d * outputs
    norms = 2 * (2 * d + w["q_rank"] + w["rank"]) + outputs  # and the bias
    layer = (2 * attention + 2 * dense + router + w["experts_held"] * expert
             + norms)
    return {
        "attention": attention, "dense_mlp": dense, "expert": expert,
        "router": router, "layer": layer, "embedding": v * d, "head": d * v,
        "total": w["layers"] * layer + 2 * v * d + d,
    }


_SHAPE_KEYS = ("vocab", "layers", "d", "n_heads", "nope", "rope", "vd", "rank",
               "q_rank", "dense_width", "expert_width", "experts",
               "experts_held", "zero", "init", "bias_init")


def _zoo_entry():
    """The program's entry for this model; a program that has none cannot
    run the configuration, and says so before anything is computed."""
    from distkeras_tpu.models import zoo

    entry = getattr(zoo, "longcat_flash_lm", None)
    if entry is None:
        raise RuntimeError(
            "the program has no zoo.longcat_flash_lm: it cannot run a "
            "configuration of the longcat_flash family")
    return entry


def make_weights(w: dict, seed):
    """Every weight from ``seed`` in one jitted call, on the default device,
    bfloat16: N(0, init), the output projections (wo, every wd) scaled by
    1/sqrt(2 L) as the other families do, RMSNorm gains 1, the router's
    selection bias N(0, bias_init) over all its outputs."""
    _zoo_entry()
    return _make_weights(jnp.uint32(int(seed) % (2**32)),
                         **{k: w[k] for k in _SHAPE_KEYS})


@functools.partial(jax.jit, static_argnames=_SHAPE_KEYS)
def _make_weights(seed, *, vocab, layers, d, n_heads, nope, rope, vd, rank,
                  q_rank, dense_width, expert_width, experts, experts_held,
                  zero, init, bias_init):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4 + 24 * layers))
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

    def half():
        return {
            "ln1": gain(d),
            "attn": {"wqa": normal((d, q_rank)), "q_norm": gain(q_rank),
                     "wqb": normal((q_rank, n_heads * (nope + rope))),
                     "wkva": normal((d, rank + rope)), "kv_norm": gain(rank),
                     "wkvb": normal((rank, n_heads * (nope + vd))),
                     "wo": normal((n_heads * vd, d), out)},
            "ln2": gain(d),
            "mlp": mlp(dense_width),
        }

    params = {"0": {"tokens": normal((vocab, d))}}
    for i in range(layers):
        params[str(i + 1)] = {
            "0": half(), "1": half(),
            "moe": {"router": {"wr": normal((d, experts + zero)),
                               "bias": normal((experts + zero,), bias_init)},
                    "experts": mlp(expert_width, (experts_held,))},
        }
    params[str(layers + 1)] = gain(d)
    params[str(layers + 2)] = {"kernel": normal((d, vocab))}
    return params


# ------------------------------------------------------------ hand-over


def build_program_model(w: dict, weights, traffic: dict):
    """The program's own model with the benchmark's seeded weights in it:
    ``zoo.longcat_flash_lm`` built under ``jax.eval_shape``, its tree checked
    leaf by leaf against the layout above, the arrays of ``make_weights`` in
    its place (bfloat16 where the program initialises float32)."""
    entry = _zoo_entry()
    holder = []

    def build():
        model = entry(
            vocab_size=w["vocab"], seq_len=w["seq"], hidden_size=w["d"],
            num_attention_heads=w["n_heads"], qk_nope_head_dim=w["nope"],
            qk_rope_head_dim=w["rope"], v_head_dim=w["vd"],
            kv_lora_rank=w["rank"], q_lora_rank=w["q_rank"],
            ffn_hidden_size=w["dense_width"],
            expert_ffn_hidden_size=w["expert_width"],
            n_routed_experts=w["experts"], zero_expert_num=w["zero"],
            moe_topk=w["top_k"], num_layers=w["layers"],
            routed_scaling_factor=w["routed_scale"], rope_theta=w["theta"],
            rms_norm_eps=w["eps"],
            mla_scale_q_lora=w["q_scale"] != 1.0,
            mla_scale_kv_lora=w["kv_scale"] != 1.0,
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
            "the program's longcat_flash_lm no longer has the tree that "
            "benchmark/families/longcat_flash.py documents: the hand-over "
            "format moved")
    model.params = weights
    return model


# -------------------------------------------------------------- forward


ROW_BLOCK_ATTN = 512  # query rows whose scores are held at once (64 heads)


def attention(p, x, w: dict, dot):
    """Expanded latent attention of one sequence, causal, with the low-rank
    query and the two factors; x is (T, d), normalised."""
    t = x.shape[0]
    h, nope, rp, vd, rank = (w["n_heads"], w["nope"], w["rope"], w["vd"],
                             w["rank"])
    p = f32(p)
    pos = jnp.arange(t)
    cq = rms_norm(dot(x, p["wqa"]), p["q_norm"]["gamma"], w["eps"])
    q = (w["q_scale"] * dot(cq, p["wqb"])).reshape(t, h, nope + rp)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], pos, w["theta"])],
                        axis=-1)
    ckv = dot(x, p["wkva"])
    cn = w["kv_scale"] * rms_norm(ckv[:, :rank], p["kv_norm"]["gamma"], w["eps"])
    k_pe = rotate(ckv[:, rank:], pos, w["theta"])
    kv = dot(cn, p["wkvb"]).reshape(t, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, h, rp))], axis=-1)
    v = kv[..., nope:]

    def rows(args):
        qb, at = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            / math.sqrt(nope + rp)
        s = jnp.where(pos[None, None, :] <= at[None, :, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)

    if t > ROW_BLOCK_ATTN and t % ROW_BLOCK_ATTN == 0:
        nb = t // ROW_BLOCK_ATTN
        o = jax.lax.map(rows, (q.reshape(nb, ROW_BLOCK_ATTN, h, nope + rp),
                               pos.reshape(nb, ROW_BLOCK_ATTN)))
    else:
        o = rows((q, pos))
    return dot(o.reshape(t, h * vd), p["wo"])


def route(p, x, w: dict, dot):
    """Softmax scores over all ``experts + zero`` outputs; the top ``k`` of
    score + bias; weights = the scale times the chosen scores, not normalised
    (``norm_topk``, which the configuration leaves false, would divide by
    their sum). Also the margin between the last output taken and the first
    left out, in score + bias. ``x`` is (T, d)."""
    k = w["top_k"]
    r = f32(p)
    s = jax.nn.softmax(dot(x, r["wr"]), axis=-1)
    biased = s + r["bias"]
    order = jnp.argsort(-biased, axis=-1)[:, :k + 1]
    chosen = order[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if w["norm_topk"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    edge = jnp.take_along_axis(biased, order[:, k - 1:], axis=-1)
    return chosen, picked * w["routed_scale"], edge[:, 0] - edge[:, 1]


def expert_layer(p, u, w: dict, dot, held=None, with_zero=True):
    """The expert layer over all tokens: the held routed experts by a plain
    pass one after another, and every identity pick as ``weight x u``; ``u``
    is (T, d). The stacked weights are those of experts ``0 .. experts_held
    - 1``; ``held`` (ids) takes a share of that stack, for the test that adds
    the shares up. Returns the output, ``route``'s margin a token, and how
    many identity experts each token picked."""
    n, e = u.shape[0], w["experts"]
    held = np.arange(w["experts_held"]) if held is None else np.asarray(held)
    chosen, weight, margin = route(p["router"], u, w, dot)
    gate = jnp.zeros((n, e + w["zero"]), jnp.float32).at[
        jnp.arange(n)[:, None], chosen].set(weight)

    def one(acc, ex):
        wg, wu, wd, g = ex
        y = gated({"wg": wg.astype(jnp.float32), "wu": wu.astype(jnp.float32),
                   "wd": wd.astype(jnp.float32)}, u, dot)
        return acc + g[:, None] * y, None

    ex = p["experts"]
    if len(held) != ex["wg"].shape[0]:
        ex = {name: stack[held] for name, stack in ex.items()}
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (ex["wg"], ex["wu"], ex["wd"], gate.T[held]))
    if with_zero:
        out = out + gate[:, e:].sum(axis=-1, keepdims=True) * u
    return out, margin, (chosen >= e).sum(axis=-1)


def _norm(p, name, x, w):
    return rms_norm(x, p[name]["gamma"].astype(jnp.float32), w["eps"])


def attention_part(p, x, w: dict, dot):
    """``x + MLA(RMSNorm(x))`` of one half of a layer."""
    return x + attention(p["attn"], _norm(p, "ln1", x, w), w, dot)


def expert_part(p, h1, w: dict, dot):
    """``(MoE(u), h1 + MLP_0(u), margin)`` with ``u = RMSNorm(h1)``: what
    reads the hidden state after the first attention."""
    u = _norm(p["0"], "ln2", h1, w)
    m, margin, _ = expert_layer(p["moe"], u, w, dot)
    return m, h1 + gated(f32(p["0"]["mlp"]), u, dot), margin


def mlp_part(p, h3, m, w: dict, dot):
    """``h3 + MLP_1(RMSNorm(h3)) + m``: the layer's end."""
    return h3 + gated(f32(p["mlp"]), _norm(p, "ln2", h3, w), dot) + m


def layer(p, x, w: dict, dot):
    """One layer over one sequence, (T, d) float32, and the expert layer's
    routing margin a token."""
    h1 = attention_part(p["0"], x, w, dot)
    m, h2, margin = expert_part(p, h1, w, dot)
    h3 = attention_part(p["1"], h2, w, dot)
    return mlp_part(p["1"], h3, m, w, dot), margin


def hidden(params, tokens, w: dict, dot=dot_highest):
    """The final RMSNorm's output for one sequence of token ids: (T, d)."""
    x = embed(params, tokens)
    for i in range(1, w["layers"] + 1):
        x, _ = layer(params[str(i)], x, w, dot)
    return final_norm(params, x, w)


_key = latent._key


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
def _part_fns(w_items: tuple, precision: str):
    """A layer's four parts as programs of their own: the forward is called a
    part at a time, so that one attention's or one MLP's weights (one
    expert's, inside the expert layer's pass) are held upcast beside the
    bfloat16 tree and no more (a whole layer upcast is 2.7e9 bytes here)."""
    w, dot = dict(w_items), get_dot(precision)
    return (jax.jit(lambda p, x: attention_part(p, x, w, dot)),
            jax.jit(lambda p, h1: expert_part(p, h1, w, dot)),
            jax.jit(lambda p, h3, m: mlp_part(p, h3, m, w, dot)))


def _residual(params, w: dict, seq, precision: str):
    """The last layer's output, a part of a layer a call, and a position's
    narrowest routing margin over the layers (``route``)."""
    attend, experts, end = _part_fns(_key(w), precision)
    x = jax.jit(embed)(params, seq)
    narrowest = jnp.full(seq.shape, jnp.inf, jnp.float32)
    for i in range(1, w["layers"] + 1):
        p = params[str(i)]
        h1 = attend(p["0"], x)
        m, h2, margin = experts(p, h1)
        x = end(p["1"], attend(p["1"], h2), m)
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


def decode_step(w: dict, batch: float, cached: float, *, weight_bytes: float,
                kv_bytes: float) -> dict:
    """One decode step for ``batch`` active sequences with ``cached`` tokens
    each in the cache (means over the window). Every dense matrix (the two
    attentions' five, the two MLPs' three, the router, the head) is read once
    and used for ``batch`` tokens. Of a layer's held experts, those that some
    token of the batch reaches are read: ``E_held x (1 - (1 - k / (E +
    Z))^batch)`` under EVEN routing (every router output equally likely for
    every token, tokens independent), which is what seeded random weights
    give and a trained router only approximates. A pick of an identity
    expert moves no byte and costs ``2 x d`` operations (the weighted sum); of
    a token's ``k`` picks ``Z / (E + Z)`` are such and ``E_held / (E + Z)``
    reach a held expert, at 2 operations a parameter. The cache is ``rank +
    rope`` values a token and ATTENTION, two a layer, read once; a cached
    token, attention and sequence costs ``heads x ((rank + rope) + rank) x
    2`` operations, and the absorbed form uses ``Wkvb`` once for the query
    and once for the output, which is 2 a parameter again. The embedding is a
    lookup.

    ``parts`` gives the same count by part: ``moe`` (router, routed experts
    reached, identity picks), ``mla`` (both attentions of every layer with
    their caches) and ``dense`` (both MLPs of every layer); the head is in
    the whole and in no part."""
    d, layers = w["d"], w["layers"]
    outputs = w["experts"] + w["zero"]
    expert = 3 * d * w["expert_width"]
    reached = w["experts_held"] * (1.0 - (1.0 - w["top_k"] / outputs) ** batch)
    router = d * outputs
    held_picks = w["top_k"] * w["experts_held"] / outputs  # a token
    zero_picks = w["top_k"] * w["zero"] / outputs
    moe = {
        "flops": layers * batch * (
            2 * router + 2 * held_picks * expert + zero_picks * 2 * d),
        "bytes": layers * (router + reached * expert) * weight_bytes,
    }
    attention = _attention_params(w)
    lat = w["rank"] + w["rope"]
    cache = 2 * layers * lat * cached * batch * kv_bytes
    mla = {
        "flops": 2 * layers * (2 * batch * attention + batch * cached
                               * w["n_heads"] * (lat + w["rank"]) * 2),
        "bytes": 2 * layers * attention * weight_bytes + cache,
    }
    mlp = 3 * d * w["dense_width"]
    dense = {"flops": 2 * layers * 2 * batch * mlp,
             "bytes": 2 * layers * mlp * weight_bytes}
    head = d * w["vocab"]
    parts = {"moe": moe, "mla": mla, "dense": dense}
    return {
        "flops": sum(p["flops"] for p in parts.values()) + 2 * batch * head,
        "bytes": sum(p["bytes"] for p in parts.values()) + head * weight_bytes,
        "weight_bytes": moe["bytes"] + mla["bytes"] - cache + dense["bytes"]
        + head * weight_bytes,
        "kv_bytes": cache, "experts_reached_a_layer": reached,
        "zero_picks_a_token_and_layer": zero_picks,
        "held_picks_a_token_and_layer": held_picks,
        "parts": parts,
    }
