"""The ``keye_vl2`` family (Hugging Face ``Kwai-Keye/Keye-VL-2.0-30B-A3B``,
``model_type: KeyeVL2``): the LANGUAGE MODEL of it, on token ids. Grouped-query
attention (32 query heads over 4 K/V heads of 128, an RMSNorm a head on q and
k) whose keys a learned indexer picks (``sa_config``: 16 heads of 64 against
ONE cached selector key a token; the ``topk`` = 2,048 positions of largest
score are attended and no others), then 128 routed experts, the top 8 by
softmax scores normalised over the picks, no shared expert. The one place in
the benchmark that knows this model: its sizes under their published keys, its
weights from the seed, its plain reference in ``jax.numpy`` and float32 under
``highest`` (no cache, no kernel, no batching; queries a block at a time, each
block's index scores, its exact selection and its attention under the
selection's mask; experts by a plain pass over the held experts), the
hand-over of those weights to the program's own model, and the operations and
bytes of a decode step. Independent of the program's block: nothing of
``distkeras_tpu`` is imported but the zoo entry that ``build_program_model``
hands the weights to. What no model owns of a routed-expert family's reference
is taken from ``families/deepseek_v3.py`` (RMSNorm, the rotation, the gated
MLP, the embedding, the final norm, the head) and the plain pass over the held experts from ``families/laguna.py``. The
vision tower is not here: the catalog's row holds none of its sizes.

The layer equations, every layer alike, ``x`` ``(T, d)``, no bias anywhere
(each departure from the published model is listed in the configuration file
under ``assumed``)::

    h = RMSNorm(x)
    q = rot(RMSNorm_head(h Wq)) (T, H, Dh);  k = rot(RMSNorm_head(h Wk)),
        v = h Wv (T, Hkv, Dh);  rot: pairs (2i, 2i+1) of a head turned by
        pos * theta^(-2i/Dh) (a text token's three position components are
        equal, so the sectioned rotation is the plain one)
    qI = rot(h WqI) (T, J, Di);  kI = rot(LayerNorm(h WkI)) (T, Di);
        w = h Ww (T, J)
    I[t, s] = (J Di)^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the topk positions s <= t of largest I[t, s]: all of them while
        t + 1 <= topk; a tie at the threshold goes to the lower position
    a[t, i] = softmax_{s in S_t}(q[t, i] . k[s, i // (H / Hkv)] / sqrt(Dh)) v[s, ..]
    x = x + concat_i(a[t, i]) Wo
    u = RMSNorm(x);  p = softmax(u Wr) over all experts; P = the top_k largest;
        w_e = p_e / sum_P p;  x = x + sum_{e in P, held} w_e E_e(u)
    last: RMSNorm, head (d, V), untied, no bias; no position table.

Weights are made bfloat16 and the reference upcasts them a layer (an expert)
at a time. The tree is what ``zoo.keye_lm`` holds::

    {"0": {"tokens": (V, d)},
     "1".."L": {"ln1": {gamma},
                "attn": {wq (d, H Dh), wk, wv (d, Hkv Dh), wo (H Dh, d),
                         q_norm: {gamma (Dh,)}, k_norm: {gamma (Dh,)},
                         index: {wq (d, J Di), wk (d, Di), ww (d, J),
                                 norm: {gamma, beta (Di,)}}},
                "ln2": {gamma},
                "ffn": {"router": {wr (d, E)},
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
    embed, f32, final_norm, logits, rms_norm, rotate)
from benchmark.families.laguna import expert_layer
from benchmark.harness import log
from benchmark.reference import (
    adam_step, dot_highest, get_dot, leaf_norms, leaf_norms_of_difference)

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------- sizes and weights


def widths(config: dict) -> dict:
    """The sizes of a configuration file under the names used here.
    ``num_experts`` counts the routed experts this chip holds of each layer
    (ids ``0 .. experts_held - 1``) where the file lists it under
    ``reduced``; the router keeps the source's ``experts`` outputs.
    ``flip_*``, ``swap_gap_limit``, ``gap_limit``: the serving check's own
    (``judged``)."""
    a = config["assumed"]
    sa = config["sa_config"]
    check = config.get("serving", {}).get("check", {})
    held = int(config["num_experts"])
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer has one selector key a token")
    return {
        **({"flip_margin_from": float(check["flip_margin_from"]),
            "flip_margin_to": float(check["flip_margin_to"]),
            "flip_floor": int(check["flip_floor"]),
            "flip_length": float(check["flip_length"]),
            "flip_length_power": float(check["flip_length_power"]),
            "flip_sigmas": float(check["flip_sigmas"]),
            "flip_share_limit": float(check["flip_share_limit"]),
            "swap_gap_limit": float(check["swap_gap_limit"]),
            "gap_limit": float(check["gap_limit"])}
           if "flip_share_limit" in check else {}),
        "vocab": int(config["vocab_size"]),
        "seq": int(config["max_position_embeddings"]),
        "layers": int(config["num_hidden_layers"]),
        "d": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]),
        "theta": float(config["rope_theta"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "experts": int(config.get("reduced_from", {}).get(
            "num_experts", [held])[0]),
        "experts_held": held,
        "top_k": int(config["num_experts_per_tok"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": 1.0,  # the config names no scale on the picks
        "eps": float(config["rms_norm_eps"]),
        "init": float(a["initializer_range"]),
    }


def _attention_params(w: dict) -> int:
    d, hd = w["d"], w["head_dim"]
    return 2 * d * w["n_heads"] * hd + 2 * d * w["kv_heads"] * hd


def _index_params(w: dict) -> int:
    j, di = w["index_heads"], w["index_dim"]
    return w["d"] * (j * di + di + j) + 2 * di


def param_count(w: dict) -> dict:
    d, v = w["d"], w["vocab"]
    expert = 3 * d * w["expert_width"]
    router = d * w["experts"]
    norms = 2 * d + 2 * w["head_dim"]
    outside = _attention_params(w) + _index_params(w) + router + norms
    layer_held = outside + w["experts_held"] * expert
    return {
        "attention": _attention_params(w), "indexer": _index_params(w),
        "router": router, "norms": norms, "expert": expert,
        "layer_outside_experts": outside, "layer_held": layer_held,
        "layer_whole": outside + w["experts"] * expert,
        "embedding": v * d, "head": d * v,
        "total": w["layers"] * layer_held + 2 * v * d + d,
    }


_SHAPE_KEYS = ("vocab", "layers", "d", "n_heads", "kv_heads", "head_dim",
               "index_heads", "index_dim", "expert_width", "experts",
               "experts_held", "init")


def _zoo_entry():
    """The program's entry for this model; a program that has none cannot
    run the configuration, and says so before anything is computed."""
    from distkeras_tpu.models import zoo

    entry = getattr(zoo, "keye_lm", None)
    if entry is None:
        raise RuntimeError(
            "the program has no zoo.keye_lm: it cannot run a configuration "
            "of the keye_vl2 family (a block whose keys an indexer selects)")
    return entry


def make_weights(w: dict, seed):
    """Every weight from ``seed`` in one jitted call, on the default device,
    bfloat16: N(0, init), the output projections (wo, every wd) scaled by
    1/sqrt(2 L) as the other families do, norm gains 1, the LayerNorm's
    shift 0."""
    _zoo_entry()
    return _make_weights(jnp.uint32(int(seed) % (2**32)),
                         **{k: w[k] for k in _SHAPE_KEYS})


@functools.partial(jax.jit, static_argnames=_SHAPE_KEYS)
def _make_weights(seed, *, vocab, layers, d, n_heads, kv_heads, head_dim,
                  index_heads, index_dim, expert_width, experts, experts_held,
                  init):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4 + 12 * layers))
    bf = jnp.bfloat16

    def normal(shape, scale=init):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(bf)

    def gain(n):
        return {"gamma": jnp.ones((n,), bf)}

    out = init / math.sqrt(2 * layers)
    params = {"0": {"tokens": normal((vocab, d))}}
    for i in range(layers):
        params[str(i + 1)] = {
            "ln1": gain(d),
            "attn": {
                "wq": normal((d, n_heads * head_dim)),
                "wk": normal((d, kv_heads * head_dim)),
                "wv": normal((d, kv_heads * head_dim)),
                "wo": normal((n_heads * head_dim, d), out),
                "q_norm": gain(head_dim), "k_norm": gain(head_dim),
                "index": {
                    "wq": normal((d, index_heads * index_dim)),
                    "wk": normal((d, index_dim)),
                    "ww": normal((d, index_heads)),
                    "norm": {"gamma": jnp.ones((index_dim,), bf),
                             "beta": jnp.zeros((index_dim,), bf)},
                },
            },
            "ln2": gain(d),
            "ffn": {
                "router": {"wr": normal((d, experts))},
                "experts": {
                    "wg": normal((experts_held, d, expert_width)),
                    "wu": normal((experts_held, d, expert_width)),
                    "wd": normal((experts_held, expert_width, d), out)},
            },
        }
    params[str(layers + 1)] = gain(d)
    params[str(layers + 2)] = {"kernel": normal((d, vocab))}
    return params


# ------------------------------------------------------------ hand-over


def build_program_model(w: dict, weights, traffic: dict):
    """The program's own model with the benchmark's seeded weights in it:
    ``zoo.keye_lm`` built under ``jax.eval_shape`` from the configuration's
    own keys, its tree checked leaf by leaf against the layout above, the
    arrays of ``make_weights`` in its place (bfloat16 where the program
    initialises float32)."""
    entry = _zoo_entry()
    holder = []

    def build():
        model = entry(
            vocab_size=w["vocab"], seq_len=w["seq"], hidden_size=w["d"],
            num_attention_heads=w["n_heads"],
            num_key_value_heads=w["kv_heads"], head_dim=w["head_dim"],
            moe_intermediate_size=w["expert_width"],
            num_experts=w["experts"], num_experts_per_tok=w["top_k"],
            num_hidden_layers=w["layers"],
            sa_config={"indexer_num_heads": w["index_heads"],
                       "indexer_head_dim": w["index_dim"],
                       "indexer_num_kv_heads": 1, "topk": w["topk"]},
            rope_theta=w["theta"], norm_topk_prob=w["norm_topk"],
            rms_norm_eps=w["eps"], qk_norm=True,
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
            "the program's keye_lm no longer has the tree that "
            "benchmark/families/keye_vl2.py documents: the hand-over format "
            "moved")
    model.params = weights
    return model


def program_control(name: str, w: dict) -> dict:
    """A control of ``correct`` that this mechanism needs, put into the
    PROGRAM alone (``benchmark/controls_select.py``; the reference keeps
    the stated model). Returns the widths the program is built from.

    - ``"selector8"``: every selector key goes into its cache rounded to 8
      bits (float8 e4m3: 3 bits of mantissa), in every process that builds
      the block after this call; the queries and the scores stay as stated;
    - ``"widen4"``: the selection widened fourfold (``topk`` x 4 rows read
      a slot and layer; at this cell's lengths most of a median request's
      cache)."""
    if name == "widen4":
        return {**w, "topk": 4 * w["topk"]}
    if name != "selector8":
        raise ValueError(f"control {name!r}: 'selector8' or 'widen4'")
    from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock as block

    stated = block.index_inputs

    def rounded(self, pi, h, pos):
        qi, ki, weights = stated(self, pi, h, pos)
        return qi, ki.astype(jnp.float8_e4m3fn).astype(jnp.float32), weights

    block.index_inputs = rounded
    return w


# -------------------------------------------------------------- forward


ROW_BLOCK_ATTN = 256  # query rows whose scores are held at once


def layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def index_inputs(p, x, w: dict, dot):
    """``qI`` ``(T, J, Di)``, ``kI`` ``(T, Di)`` and the heads' weights
    ``(T, J)`` with the score's scale in them; ``x`` is (T, d), normalised."""
    t = x.shape[0]
    j, di = w["index_heads"], w["index_dim"]
    pos = jnp.arange(t)
    qi = rotate(dot(x, p["wq"]).reshape(t, j, di), pos, w["theta"])
    ki = layer_norm(dot(x, p["wk"]), p["norm"]["gamma"], p["norm"]["beta"],
                    w["eps"])
    ki = rotate(ki, pos, w["theta"])
    return qi, ki, dot(x, p["ww"]) / math.sqrt(j * di)


def selection(scores, see, k: int):
    """Which keys a query reads: ``scores`` ``(Q, T)``, ``see`` ``(Q, T)``
    (the causal mask) -> ``(Q, T)`` bool, the ``k`` visible keys of largest
    score (all of them where fewer are visible); among keys AT the ``k``-th
    largest score, those of lower position, as many as there is room for."""
    t = scores.shape[-1]
    k = min(k, t)
    masked = jnp.where(see, scores, -jnp.inf)
    kth = jnp.sort(masked, axis=-1)[:, t - k][:, None]
    above = masked > kth
    ties = (masked == kth) & see
    room = k - above.sum(axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))


def attention(p, x, w: dict, dot, select: bool = True, qk_norm: bool = True):
    """Grouped-query attention of one sequence over the positions its
    indexer selects; ``x`` is (T, d), normalised. ``select`` False attends
    every visible key and ``qk_norm`` False leaves the heads' norms out: the
    model without that part, for the tests that show each part matters."""
    t = x.shape[0]
    nh, kvh, hd = w["n_heads"], w["kv_heads"], w["head_dim"]
    g = nh // kvh
    p = f32(p)
    pos = jnp.arange(t)
    q = dot(x, p["wq"]).reshape(t, nh, hd)
    k = dot(x, p["wk"]).reshape(t, kvh, hd)
    if qk_norm:
        q = rms_norm(q, p["q_norm"]["gamma"], w["eps"])
        k = rms_norm(k, p["k_norm"]["gamma"], w["eps"])
    q, k = rotate(q, pos, w["theta"]), rotate(k, pos, w["theta"])
    v = dot(x, p["wv"]).reshape(t, kvh, hd)
    qi, ki, wi = index_inputs(p["index"], x, w, dot)

    def rows(args):
        qb, qib, wib, at = args
        see = pos[None, :] <= at[:, None]
        if select:
            dots = jnp.einsum("qjd,sd->qjs", qib, ki, precision=HIGHEST)
            scores = jnp.sum(jax.nn.relu(dots) * wib[:, :, None], axis=1)
            see = selection(scores, see, w["topk"])
        qg = qb.reshape(qb.shape[0], kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) \
            / math.sqrt(hd)
        s = jnp.where(see[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", a, v, precision=HIGHEST)
        return o.reshape(qb.shape[0], nh, hd)

    if t > ROW_BLOCK_ATTN and t % ROW_BLOCK_ATTN == 0:
        nb = t // ROW_BLOCK_ATTN
        o = jax.lax.map(rows, tuple(
            a.reshape(nb, ROW_BLOCK_ATTN, *a.shape[1:])
            for a in (q, qi, wi, pos)))
        o = o.reshape(t, nh, hd)
    else:
        o = rows((q, qi, wi, pos))
    return dot(o.reshape(t, nh * hd), p["wo"])


def layer(p, x, w: dict, dot, **parts):
    """One layer over one sequence, (T, d) float32, and the expert layer's
    routing margin a token."""
    g1 = p["ln1"]["gamma"].astype(jnp.float32)
    x = x + attention(p["attn"], rms_norm(x, g1, w["eps"]), w, dot, **parts)
    u = rms_norm(x, p["ln2"]["gamma"].astype(jnp.float32), w["eps"])
    y, margin = expert_layer(p["ffn"], u, w, dot, with_shared=False)
    return x + y, margin


def hidden(params, tokens, w: dict, dot=dot_highest, **parts):
    """The final RMSNorm's output for one sequence of token ids: (T, d)."""
    x = embed(params, tokens)
    for i in range(w["layers"]):
        x, _ = layer(params[str(i + 1)], x, w, dot, **parts)
    return final_norm(params, x, w)


def _key(w: dict) -> tuple:
    return tuple(sorted(w.items()))


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
    the tiny size of the tests: no cell trains this family (the selection
    passes no gradient to the indexer, which the published model trains
    with a loss of its own that the row does not give)."""
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
def _layer_fn(w_items: tuple, precision: str):
    """One layer as a program of its own: the forward is called a layer at a
    time, so that one layer's weights (one expert's, inside the expert
    layer's pass) are held upcast beside the bfloat16 tree and no more."""
    w = dict(w_items)
    return jax.jit(lambda p, x: layer(p, x, w, get_dot(precision)))


def _residual(params, w: dict, seq, precision: str):
    """The last layer's output, a layer a call."""
    fn = _layer_fn(_key(w), precision)
    x = jax.jit(embed)(params, seq)
    for i in range(w["layers"]):
        x, _ = fn(params[str(i + 1)], x)
    return x


def served_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """As the ``deepseek_v3`` family's: for one finished request, one full
    forward of the reference; at each served position how far the served
    token's logit lies below the reference's largest, the same for a
    ``control`` precision's first token, and (where that family gives the
    narrowest routing margin, which tells nothing here: this router is so
    flat that every token lies within 1e-3 of a tie) the reference's OWN
    margin at the position, its largest logit less its second largest."""
    key = _key(w)
    n = len(sequence)
    bucket = latent.SEQ_BUCKET
    while bucket < n:
        bucket *= 2
    padded = np.zeros(min(w["seq"], bucket), np.int32)
    padded[:n] = sequence  # causal: what follows a position cannot reach it
    seq = jnp.asarray(padded)
    ref_x = _residual(params, w, seq, "highest")
    low_x = _residual(params, w, seq, control) if control else None
    served = np.asarray(sequence[prompt_len:], np.int64)
    positions = np.arange(prompt_len - 1, n - 1)
    block = latent.ROW_BLOCK
    gaps, control_gaps, margins = [], [], []
    for i in range(0, len(positions), block):
        pos = positions[i:i + block]
        rows = np.zeros(block, np.int32)
        rows[:len(pos)] = pos
        ref = np.asarray(latent._logits_fn(key, "highest")(params, ref_x, rows))[:len(pos)]
        top = np.partition(ref, -2, axis=-1)[:, -2:]
        best = top[:, 1]
        margins.append(best - top[:, 0])
        at = np.arange(len(pos))
        gaps.append(best - ref[at, served[i:i + block]])
        if control:
            low = np.asarray(latent._logits_fn(key, control)(params, low_x, rows))
            control_gaps.append(best - ref[at, low[:len(pos)].argmax(axis=-1)])
    return (np.concatenate(gaps),
            np.concatenate(control_gaps) if control else None,
            np.concatenate(margins))


def judged(gaps, w: dict, margins=None, prompt_len: int = 0):
    """A request's gaps as the serving check takes them: the harness holds
    the widest of what comes back to the one limit ``gap_limit``.

    What ``deepseek_v3.judged`` holds (the gap a given share down a
    request's ranks) cannot tell this program from its own weights rounded
    to 8 bits (PERF.md section 2): either differs from the reference in some
    requests, by gaps of one size, and in others by nothing, because whether
    a served token differs depends first on how far the REFERENCE's own best
    leads its second best there, which is the request's luck and no one's
    precision. So the positions are taken by that margin. Where it is
    ``flip_margin_from`` to ``flip_margin_to`` logits (wide enough that the
    stated precision's noise seldom overcomes it, narrow enough that the
    next precision's often does), the share of positions at which the
    program served another token than the reference's best is counted; a
    request with fewer than ``flip_floor`` such positions says nothing (0).
    The count is taken at what it proves: ``flip_sigmas`` standard deviations
    of a count (its square root) under what was counted, since six positions
    of 275 say little of a share and a hundred and seventy of 450 say much.
    The stated precision's own share grows with the cached length beyond
    ``flip_length``, about by its square (the selector's keys are cached in
    bfloat16 and the reference selects from float32 ones: the longer the
    cache, the more keys lie within the rounding of the selection's
    threshold), on top of what every other rounding gives at any length, and
    the weights' rounding adds a share that does not grow: so the share is
    divided by ``(prompt_len / flip_length) ** flip_length_power`` WHERE THAT
    IS OVER 1 (a short prompt's share is held as it is: the square law is
    the selector's part alone, and dividing a 7,899-token prompt's ordinary
    1.28% by its 0.23 read 0.055 in a sound run, PERF.md section 6) and then
    held to ``flip_share_limit``. Besides, the request's widest gap is held
    to ``swap_gap_limit``, which a token that the precision's noise moved
    passes and a token drawn at random does not. Both come back scaled to
    ``gap_limit``. A configuration whose ``serving.check`` states no
    ``flip_share_limit`` has its gaps back as they are."""
    if "flip_share_limit" not in w:
        return gaps
    if margins is None:  # ``controls_rounded.py --dump`` knows none
        raise ValueError(
            "keye_vl2.judged takes the reference's margins with the gaps: "
            "dump this family through benchmark/controls_select.py")
    gaps = np.asarray(gaps, np.float64)
    margins = np.asarray(margins, np.float64)
    near = (margins >= w["flip_margin_from"]) & (margins < w["flip_margin_to"])
    n = int(near.sum())
    flips = int(np.count_nonzero(gaps[near]))
    by_length = max(
        1.0, (prompt_len / w["flip_length"]) ** w["flip_length_power"])
    proven = max(0.0, flips - w["flip_sigmas"] * flips ** 0.5)
    share = proven / n / by_length if n >= w["flip_floor"] else 0.0
    widest = float(gaps.max()) if len(gaps) else 0.0
    log(f"check, one request: prompt {prompt_len}, {len(gaps)} served "
        f"tokens, {np.count_nonzero(gaps)} not the reference's best; of {n} "
        f"where the reference's margin is {w['flip_margin_from']} to "
        f"{w['flip_margin_to']}, {flips} ({proven:.4g} proven); over "
        f"{by_length:.4g} for the length: share {share:.6g} (limit "
        f"{w['flip_share_limit']}; at least {w['flip_floor']} counted); "
        f"widest gap {widest:.6g} (limit {w['swap_gap_limit']})")
    # as long as the request, so that the harness counts its tokens right
    out = np.zeros(max(len(gaps), 2))
    out[0] = share * w["gap_limit"] / w["flip_share_limit"]
    out[1] = widest * w["gap_limit"] / w["swap_gap_limit"]
    return out


def token_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """``served_gaps`` as ``judged``: what the serving check takes the
    widest of."""
    gaps, control_gaps, margins = served_gaps(
        params, w, sequence, prompt_len, control)
    return judged(gaps, w, margins, prompt_len), control_gaps


# --------------------------------------------------- operations and bytes
#
# Counted from the algorithm, never from the compiler's cost analysis
# (``flops.py`` says how).


def decode_step(w: dict, batch: float, cached: float, *, weight_bytes: float,
                kv_bytes: float) -> dict:
    """One decode step for ``batch`` active sequences with ``cached`` tokens
    each in the cache (means over the window). Every matrix but the routed
    experts' is read once and used for ``batch`` tokens. ``parts``:

    - ``index``: the indexer's three matrices and EVERY cached selector key
      of every active slot (``Di`` values a token and layer, read once); a
      cached position costs ``J`` heads ``Di x 2`` operations for its score;
    - ``attn``: the four matrices and ``min(cached, topk)`` key and value
      rows a slot and layer (``2 x Hkv x Dh`` values a row): what the
      selection leaves to read; a row read costs ``H`` query heads ``Dh x
      2`` operations for its score and as many for the weighted sum;
    - ``moe``: the router and, of a layer's held experts, those that some
      token of the batch reaches, ``E_held x (1 - (1 - k / E)^batch)`` under
      EVEN routing, at 2 operations a parameter for the ``k x E_held / E``
      picks of a token that reach a held expert; no shared expert;
    - ``head``. The embedding is a lookup.

    They sum to the whole."""
    d, hd, kvh, nh = w["d"], w["head_dim"], w["kv_heads"], w["n_heads"]
    layers = w["layers"]
    reach = min(cached, w["topk"])
    index_cache = layers * w["index_dim"] * cached * batch * kv_bytes
    index = {
        "flops": layers * (2 * batch * _index_params(w) + batch * cached
                           * w["index_heads"] * w["index_dim"] * 2),
        "bytes": layers * _index_params(w) * weight_bytes + index_cache,
    }
    attn_cache = layers * 2 * kvh * hd * reach * batch * kv_bytes
    attn = {
        "flops": layers * (2 * batch * _attention_params(w)
                           + batch * reach * nh * hd * 4),
        "bytes": layers * _attention_params(w) * weight_bytes + attn_cache,
    }
    expert = 3 * d * w["expert_width"]
    router = d * w["experts"]
    reached = w["experts_held"] * (
        1.0 - (1.0 - w["top_k"] / w["experts"]) ** batch)
    held_share = w["experts_held"] / w["experts"]
    moe = {
        "flops": layers * 2 * batch * (
            router + w["top_k"] * held_share * expert),
        "bytes": layers * (router + reached * expert) * weight_bytes,
    }
    head = {"flops": 2 * batch * d * w["vocab"],
            "bytes": d * w["vocab"] * weight_bytes}
    parts = {"index": index, "attn": attn, "moe": moe, "head": head}
    total_bytes = sum(p["bytes"] for p in parts.values())
    cache = index_cache + attn_cache
    return {
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": total_bytes, "weight_bytes": total_bytes - cache,
        "kv_bytes": cache, "experts_reached_a_layer": reached,
        "rows_read_a_slot": reach, "parts": parts,
    }
