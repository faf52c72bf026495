"""The ``deepseek_v3`` family (Hugging Face ``model_type: deepseek_v3``):
latent attention, a leading dense layer, then routed experts with shared
experts. The one place in the benchmark that knows this model: its sizes
under their published keys, its weights from the seed, its plain reference in
``jax.numpy`` and float32 under ``highest`` (expanded attention, no cache, no
kernels, experts by a plain pass over the held experts), the hand-over of
those weights to the program's own model, and the operations and bytes of a
decode step. Independent of the program's block: nothing of
``distkeras_tpu`` is imported but the zoo entry that ``build_program_model``
hands the weights to.

The layer equations (each departure from the published model is listed in
the configuration file under ``assumed``):

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g
    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attn:  q = x Wq as H heads of [q_nope | q_pe];  x Wkva = [c | k_pe], one
           k_pe for all heads;  cn = RMSNorm(c);  cn Wkvb as H heads of
           [k_nope | v];  q_pe, k_pe rotated by position, pairs (2i, 2i+1)
           turned by pos * theta^(-2i/rope);  k = [k_nope | k_pe];  scores
           q.k / sqrt(nope + rope), causal, softmax;  o = sum w v, times Wo
    FFN:   layer < first_k_dense_replace: (silu(x Wg) * (x Wu)) Wd
           else: s = sigmoid(x Wr); chosen = top k of s + b; weights =
           s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor;
           sum of weight x expert(x) over the chosen experts that are held,
           plus the shared gated MLP (n_shared x expert width) on every token
    last:  RMSNorm, head (d, V), untied, no bias; no position table.

Weights are made bfloat16 (the model's published dtype; a float32 tree of
the cell's 5.07e9 parameters fits no chip) and the reference upcasts them,
a layer (an expert) at a time. The tree is what ``zoo.mla_moe_lm`` holds:

    {"0": {"tokens": (V, d)},
     "1".."L": {"ln1": {gamma}, "attn": {wq, wkva, kv_norm: {gamma}, wkvb, wo},
                "ln2": {gamma},
                "ffn": {wg, wu, wd}                      (dense layers)
                     | {"router": {wr (d, E), bias (E,)},
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

from benchmark.harness import log
from benchmark.reference import (
    _tree_add, adam_step, dot_highest, get_dot, leaf_norms,
    leaf_norms_of_difference)

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------- sizes and weights


def widths(config: dict) -> dict:
    """The sizes of a configuration file under the names used here.
    ``experts_held`` counts the routed experts this chip holds of each
    expert layer (ids ``0 .. experts_held - 1``); the router keeps its
    ``experts`` outputs. ``swap_*``: how ``token_gaps`` judges a request's
    gaps, from the configuration's ``serving.check`` where it gives them."""
    a = config["assumed"]
    check = config.get("serving", {}).get("check", {})
    return {
        **({"swap_share": float(check["swap_share"]),
            "swap_floor": int(check["swap_floor"]),
            "swap_gap_limit": float(check["swap_gap_limit"]),
            "gap_limit": float(check["gap_limit"])}
           if "swap_share" in check else {}),
        "vocab": int(config["vocab_size"]),
        "seq": int(config["max_position_embeddings"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "d": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vd": int(config["v_head_dim"]),
        "rank": int(config["kv_lora_rank"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "experts": int(config["n_routed_experts"]),
        "experts_held": int(a.get("experts_held", config["n_routed_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["n_shared_experts"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "init": float(a["initializer_range"]),
        "bias_init": float(a["router_bias_std"]),
    }


def _attention_params(w: dict) -> int:
    d, h = w["d"], w["n_heads"]
    return (d * h * (w["nope"] + w["rope"]) + d * (w["rank"] + w["rope"])
            + w["rank"] * h * (w["nope"] + w["vd"]) + h * w["vd"] * d)


def param_count(w: dict) -> dict:
    d, v, e = w["d"], w["vocab"], w["experts_held"]
    attention = _attention_params(w)
    expert = 3 * d * w["expert_width"]
    moe = d * w["experts"] + w["shared"] * expert + e * expert
    norms = 2 * d + w["rank"]
    dense_layer = attention + 3 * d * w["dense_width"] + norms
    expert_layer = attention + moe + w["experts"] + norms
    n_moe = w["layers"] - w["dense_layers"]
    return {
        "attention": attention, "expert": expert, "dense_layer": dense_layer,
        "expert_layer": expert_layer, "embedding": v * d, "head": d * v,
        "total": (w["dense_layers"] * dense_layer + n_moe * expert_layer
                  + 2 * v * d + d),
    }


_SHAPE_KEYS = ("vocab", "layers", "dense_layers", "d", "n_heads", "nope",
               "rope", "vd", "rank", "dense_width", "expert_width", "experts",
               "experts_held", "shared", "init", "bias_init")


def make_weights(w: dict, seed):
    """Every weight from ``seed`` in one jitted call, on the default device,
    bfloat16: N(0, init), the output projections (wo, every wd) scaled by
    1/sqrt(2 L) as the GPT-2 family does, RMSNorm gains 1, the router's
    selection bias N(0, bias_init)."""
    return _make_weights(jnp.uint32(int(seed) % (2**32)),
                         **{k: w[k] for k in _SHAPE_KEYS})


@functools.partial(jax.jit, static_argnames=_SHAPE_KEYS)
def _make_weights(seed, *, vocab, layers, dense_layers, d, n_heads, nope, rope,
                  vd, rank, dense_width, expert_width, experts, experts_held,
                  shared, init, bias_init):
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
        if i < dense_layers:
            ffn = mlp(dense_width)
        else:
            ffn = {"router": {"wr": normal((d, experts)),
                              "bias": normal((experts,), bias_init)},
                   "experts": mlp(expert_width, (experts_held,)),
                   "shared": mlp(shared * expert_width)}
        params[str(i + 1)] = {
            "ln1": gain(d),
            "attn": {"wq": normal((d, n_heads * (nope + rope))),
                     "wkva": normal((d, rank + rope)),
                     "kv_norm": gain(rank),
                     "wkvb": normal((rank, n_heads * (nope + vd))),
                     "wo": normal((n_heads * vd, d), out)},
            "ln2": gain(d),
            "ffn": ffn,
        }
    params[str(layers + 1)] = gain(d)
    params[str(layers + 2)] = {"kernel": normal((d, vocab))}
    return params


# ------------------------------------------------------------ hand-over


def build_program_model(w: dict, weights, traffic: dict):
    """The program's own model with the benchmark's seeded weights in it.
    ``zoo.mla_moe_lm`` is built under ``jax.eval_shape`` (its own random
    initialisation is neither computed nor held), its tree is checked leaf
    by leaf against the layout above, and the arrays made by
    ``make_weights`` take its place: the same arrays, bfloat16 where the
    program initialises float32, which is what it serves."""
    from distkeras_tpu.models import zoo

    holder = []

    def build():
        model = zoo.mla_moe_lm(
            vocab_size=w["vocab"], seq_len=w["seq"], hidden_size=w["d"],
            num_heads=w["n_heads"], qk_nope_head_dim=w["nope"],
            qk_rope_head_dim=w["rope"], v_head_dim=w["vd"],
            kv_lora_rank=w["rank"], intermediate_size=w["dense_width"],
            moe_intermediate_size=w["expert_width"],
            n_routed_experts=w["experts"], num_experts_per_tok=w["top_k"],
            n_shared_experts=w["shared"], num_layers=w["layers"],
            first_k_dense=w["dense_layers"],
            routed_scaling_factor=w["routed_scale"], rope_theta=w["theta"],
            rms_norm_eps=w["eps"],
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
            "the program's mla_moe_lm no longer has the tree that "
            "benchmark/families/deepseek_v3.py documents: the hand-over "
            "format moved")
    model.params = weights
    return model


# -------------------------------------------------------------- forward


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotate(x, pos, theta):
    """Pairs ``(2i, 2i+1)`` of the last axis turned by ``pos *
    theta^(-2i/n)``; ``x`` is (T, ..., n), ``pos`` (T,)."""
    n = x.shape[-1]
    inv = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * jnp.asarray(inv, jnp.float32)
    pairs = x.reshape(x.shape[:-1] + (n // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def gated(p, x, dot):
    return dot(jax.nn.silu(dot(x, p["wg"])) * dot(x, p["wu"]), p["wd"])


ROW_BLOCK_ATTN = 1024  # query rows whose scores are held at once


def attention(p, x, w: dict, dot):
    """Expanded latent attention of one sequence, causal; x is (T, d)."""
    t = x.shape[0]
    h, nope, rp, vd, rank = (w["n_heads"], w["nope"], w["rope"], w["vd"],
                             w["rank"])
    p = f32(p)
    pos = jnp.arange(t)
    q = dot(x, p["wq"]).reshape(t, h, nope + rp)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], pos, w["theta"])],
                        axis=-1)
    ckv = dot(x, p["wkva"])
    cn = rms_norm(ckv[:, :rank], p["kv_norm"]["gamma"], w["eps"])
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
    """Sigmoid scores over all routed experts; the top ``k`` of score + bias;
    weights = chosen scores over their sum, times the scale. Also how
    narrowly the choice was made: the margin between the last expert taken
    and the first left out, in score + bias. ``x`` is (T, d)."""
    k = w["top_k"]
    r = f32(p)
    s = jax.nn.sigmoid(dot(x, r["wr"]))
    biased = s + r["bias"]
    order = jnp.argsort(-biased, axis=-1)[:, :k + 1]
    chosen = order[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) \
        * w["routed_scale"]
    edge = jnp.take_along_axis(biased, order[:, k - 1:], axis=-1)
    return chosen, weight, edge[:, 0] - edge[:, 1]


def expert_layer(p, x, w: dict, dot, held=None, with_shared=True):
    """The routed experts over all tokens, a plain pass over the experts one
    after another, plus the shared experts; ``x`` is (T, d). The stacked
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


def layer(p, x, w: dict, dot):
    """One block over one sequence, (T, d) float32, and the expert layer's
    routing margin a token (``route``; infinite for a dense layer, which
    chooses nothing)."""
    x = x + attention(p["attn"],
                      rms_norm(x, p["ln1"]["gamma"].astype(jnp.float32), w["eps"]),
                      w, dot)
    h = rms_norm(x, p["ln2"]["gamma"].astype(jnp.float32), w["eps"])
    if "router" in p["ffn"]:
        y, margin = expert_layer(p["ffn"], h, w, dot)
    else:
        y = gated(f32(p["ffn"]), h, dot)
        margin = jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    return x + y, margin


def embed(params, tokens):
    return params["0"]["tokens"][tokens].astype(jnp.float32)


def final_norm(params, x, w: dict):
    g = params[str(w["layers"] + 1)]["gamma"].astype(jnp.float32)
    return rms_norm(x, g, w["eps"])


def hidden(params, tokens, w: dict, dot=dot_highest, remat: bool = False):
    """The final RMSNorm's output for one sequence of token ids: (T, d)."""
    x = embed(params, tokens)
    blk = functools.partial(layer, w=w, dot=dot)
    if remat:
        blk = jax.checkpoint(blk)
    for i in range(1, w["layers"] + 1):
        x, _ = blk(params[str(i)], x)
    return final_norm(params, x, w)


def logits(params, h, w: dict, dot=dot_highest):
    return dot(h, params[str(w["layers"] + 2)]["kernel"].astype(jnp.float32))


def sequence_loss(params, tokens, w: dict, dot=dot_highest):
    """Mean next-token cross-entropy of one sequence (T - 1 predictions)."""
    h = hidden(params, tokens, w, dot, remat=True)
    logp = jax.nn.log_softmax(logits(params, h[:-1], w, dot), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


# ------------------------------------------------------------- training


def _key(w: dict) -> tuple:
    return tuple(sorted(w.items()))


@functools.lru_cache(maxsize=None)
def _row_grad_fn(w_items: tuple, precision: str):
    w = dict(w_items)
    dot = get_dot(precision)
    return jax.jit(jax.value_and_grad(
        lambda p, row: sequence_loss(p, row, w, dot)))


def batch_grads(params, batch, w: dict, precision: str = "highest"):
    """Loss and gradients of one batch (B, T), a row at a time."""
    fn = _row_grad_fn(_key(w), precision)
    loss, grads = None, None
    for row in np.asarray(batch):
        l, g = fn(params, jnp.asarray(row, jnp.int32))
        loss = l if loss is None else loss + l
        grads = g if grads is None else _tree_add(grads, g)
    n = float(len(batch))
    return loss / n, jax.tree.map(lambda x: x / n, grads)


def train_readings(w: dict, seed, batches, lr: float,
                   precision: str = "highest", moment_after: int = 1) -> dict:
    """What a training check compares, computed by the reference over a
    float32 copy of the seeded weights: each step's loss, the norm of every
    leaf of Adam's first moment after ``moment_after`` steps, and of the
    parameters' change after the last. For the tiny size of the tests: no
    cell trains this family, and 16 bytes a parameter of float32 state fit
    no chip at the serving cell's size."""
    start = f32(make_weights(w, seed))
    params = jax.tree.map(jnp.copy, start)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    losses, moment_norms = [], None
    for i, batch in enumerate(batches):
        loss, grads = batch_grads(params, batch, w, precision)
        losses.append(float(loss))
        params, mu, nu, count = adam_step(params, grads, mu, nu, count, lr=lr)
        del grads
        if i + 1 == moment_after:
            moment_norms = np.asarray(leaf_norms(mu))
    change = np.asarray(leaf_norms_of_difference(params, start))
    return {"losses": losses, "moment_norms": moment_norms,
            "change_norms": change}


# -------------------------------------------------------------- serving


SEQ_BUCKET = 512   # sequences are padded to 512 x a power of two
ROW_BLOCK = 256    # positions whose logits are held at once


@functools.lru_cache(maxsize=None)
def _layer_fn(w_items: tuple, precision: str):
    """One block as a program of its own: the forward is called a layer at a
    time, so that only one layer's weights (one expert's, inside the expert
    layer's pass) are ever held upcast beside the bfloat16 tree."""
    w = dict(w_items)
    return jax.jit(lambda p, x: layer(p, x, w, get_dot(precision)))


@functools.lru_cache(maxsize=None)
def _logits_fn(w_items: tuple, precision: str):
    w = dict(w_items)
    return jax.jit(lambda p, x, rows: logits(
        p, final_norm(p, x[rows], w), w, get_dot(precision)))


def _residual(params, w: dict, seq, precision: str):
    """The last block's output, a layer a call, and a position's narrowest
    routing margin over the expert layers (``route``)."""
    fn = _layer_fn(_key(w), precision)
    x = jax.jit(embed)(params, seq)
    narrowest = jnp.full(seq.shape, jnp.inf, jnp.float32)
    for i in range(1, w["layers"] + 1):
        x, margin = fn(params[str(i)], x)
        narrowest = jnp.minimum(narrowest, margin)
    return x, narrowest


def served_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """For one finished request (prompt + served tokens), one full forward
    of the reference: at each served position, how far the served token's
    logit lies below the reference's largest. With ``control`` (a precision
    name) also the same gap for the token which that precision puts first
    at each position of the same prompt and tokens. Last: each served
    position's narrowest routing margin in the reference."""
    key = _key(w)
    n = len(sequence)
    bucket = SEQ_BUCKET
    while bucket < n:
        bucket *= 2
    padded = np.zeros(min(w["seq"], bucket), np.int32)
    padded[:n] = sequence  # causal: what follows a position cannot reach it
    seq = jnp.asarray(padded)
    ref_x, narrowest = _residual(params, w, seq, "highest")
    low_x = _residual(params, w, seq, control)[0] if control else None
    served = np.asarray(sequence[prompt_len:], np.int64)
    positions = np.arange(prompt_len - 1, n - 1)
    gaps, control_gaps = [], []
    for i in range(0, len(positions), ROW_BLOCK):
        pos = positions[i:i + ROW_BLOCK]
        rows = np.zeros(ROW_BLOCK, np.int32)
        rows[:len(pos)] = pos
        ref = np.asarray(_logits_fn(key, "highest")(params, ref_x, rows))[:len(pos)]
        best = ref.max(axis=-1)
        at = np.arange(len(pos))
        gaps.append(best - ref[at, served[i:i + ROW_BLOCK]])
        if control:
            low = np.asarray(_logits_fn(key, control)(params, low_x, rows))
            control_gaps.append(best - ref[at, low[:len(pos)].argmax(axis=-1)])
    return (np.concatenate(gaps),
            np.concatenate(control_gaps) if control else None,
            np.asarray(narrowest)[positions])


def judged(gaps, w: dict):
    """A request's gaps as the serving check takes them: the harness holds
    the widest of what comes back to the one limit ``gap_limit``.

    A routed-expert model is not continuous in its activations. Where the
    last expert taken and the first left out lie within the stated
    precision's noise, the program takes the other one, and that token's
    logits move by tenths: its gap is a swapped expert's size, whatever the
    rounding's (PERF.md section 2). Served at the stated precision one token
    of eight is not the reference's best, at the next below one of two. So the widest
    ``swap_share`` of a request's gaps (of ``swap_floor`` tokens, where it
    has fewer) are held to ``swap_gap_limit``, which a swapped expert passes
    and a token drawn at random does not: they come back scaled by
    ``gap_limit / swap_gap_limit``. The others come back as they are and are
    held to ``gap_limit``. A configuration whose ``serving.check`` states no
    ``swap_share`` has its gaps back as they are."""
    if "swap_share" not in w:
        return gaps
    gaps = np.asarray(gaps, np.float64)
    k = math.ceil(w["swap_share"] * max(len(gaps), w["swap_floor"]))
    order = np.argsort(-gaps)
    out = gaps.copy()
    out[order[:k]] *= w["gap_limit"] / w["swap_gap_limit"]
    log(f"check, one request: {len(gaps)} served tokens, "
        f"{np.count_nonzero(gaps)} not the reference's best; widest gap "
        f"{gaps[order[0]]:.6g} (limit {w['swap_gap_limit']}); widest but "
        f"{k} {gaps[order[k]] if k < len(gaps) else 0.0:.6g} (limit "
        f"{w['gap_limit']})")
    return out


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
    each in the cache (means over the window). Every matrix but the routed
    experts' is read once and used for ``batch`` tokens. Of each expert
    layer's held experts, those that some token of the batch reaches are
    read: ``E_held x (1 - (1 - k/E)^batch)`` under EVEN routing (every
    expert equally likely for every token, tokens independent), which is
    what seeded random weights give and a trained router only approximates.
    The cache is ``rank + rope`` values a token and layer, read once. A
    token's operations are 2 a parameter it uses (the routed experts' ``k``
    among them; the absorbed form uses ``Wkvb`` once for the query and once
    for the output, which is 2 a parameter again) and, a cached token, layer
    and sequence, ``heads x ((rank + rope) + rank) x 2`` for the scores over
    the latent and the weighted sum of it. The embedding is a lookup.

    ``parts`` gives the same count by part: ``moe`` (router, routed and
    shared experts of every expert layer) and ``mla`` (the attention of every
    layer with its cache); the leading dense MLP and the head are in the
    whole and in neither part."""
    d, layers = w["d"], w["layers"]
    n_moe = layers - w["dense_layers"]
    expert = 3 * d * w["expert_width"]
    reached = w["experts_held"] * (1.0 - (1.0 - w["top_k"] / w["experts"]) ** batch)
    held_share = w["experts_held"] / w["experts"]
    router, shared = d * w["experts"], w["shared"] * expert
    moe = {
        "flops": n_moe * 2 * batch * (
            router + shared + w["top_k"] * held_share * expert),
        "bytes": n_moe * (router + shared + reached * expert) * weight_bytes,
    }
    attention = _attention_params(w)
    lat = w["rank"] + w["rope"]
    cache = layers * lat * cached * batch * kv_bytes
    mla = {
        "flops": layers * (2 * batch * attention + batch * cached
                           * w["n_heads"] * (lat + w["rank"]) * 2),
        "bytes": layers * attention * weight_bytes + cache,
    }
    rest = w["dense_layers"] * 3 * d * w["dense_width"] + d * w["vocab"]
    return {
        "flops": moe["flops"] + mla["flops"] + 2 * batch * rest,
        "bytes": moe["bytes"] + mla["bytes"] + rest * weight_bytes,
        "weight_bytes": moe["bytes"] + layers * attention * weight_bytes
        + rest * weight_bytes,
        "kv_bytes": cache, "experts_reached_a_layer": reached,
        "parts": {"moe": moe, "mla": mla},
    }
