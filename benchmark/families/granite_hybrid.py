"""The ``granite_hybrid`` family (Hugging Face ``ibm-granite/granite-4.0-h-micro``,
``model_type: granitemoehybrid`` with ``num_local_experts: 0``): Mamba-2
layers, whose memory is a state of fixed size a sequence, beside a few layers
of grouped-query attention without any positional encoding, every layer
followed by a gated MLP, with Granite's four multipliers and the embedding as
the head. The one place in the benchmark that knows this model: its sizes
under their published keys, its weights from the seed, its plain reference in
``jax.numpy`` and float32 under ``highest`` (the recurrence as a
``lax.scan`` over POSITIONS, never the chunk form; every key at once under a
mask, no cache, no kernel, no batching, a layer at a time), the hand-over of
those weights to the program's own model, and the operations and bytes of a
decode step and of a prefill chunk. Independent of the program's blocks:
nothing of ``distkeras_tpu`` is imported but the zoo entry that
``build_program_model`` hands the weights to. What no model owns of a
reference (RMSNorm, the gated MLP, upcasting) is taken from
``families/deepseek_v3.py``.

The layer equations (each departure from the published model is listed in the
configuration file under ``assumed``); ``rms`` has eps ``rms_norm_eps`` and a
plain gain, no bias anywhere but the convolution's::

    x0 = embedding_multiplier * E[token]
    layer l:  x = x + residual_multiplier * mixer_l(rms(x; g1))
              x = x + residual_multiplier * (silu(u Wg) * (u Wu)) Wd,
                  u = rms(x; g2)
    logits = rms(x; gf) E^T / logits_scaling

    mamba (H heads of P, state N, one group, inner H P, C = H P + 2 N):
      [z | xBC | dt] = u W_in               (widths H P, C, H)
      xBC_t = silu(b + sum_{j<K} w[j] * xBC_{t-(K-1)+j})   depthwise, causal,
              zeros before the sequence's start;  split [x (H, P) | B (N) | C (N)]
      D_t = softplus(dt_t + dt_bias) (H,);  a_t = exp(D_t A),  A = -exp(A_log)
      S_t = a_t S_{t-1} + D_t (x_t (x) B_t)   a head (P, N);  S_{-1} = 0
      y_t = S_t C_t + D x_t
      out = rms(y * silu(z); gn) W_out      (over all H P values: one group;
                                             the gate before the norm)
    attention (Hq query heads over Hkv K/V heads of Dh):
      q = u Wq, k = u Wk, v = u Wv;  NO rotation, no position table
      a[t, j] = softmax_s(q[t, j] . k[s, j // (Hq / Hkv)] * attention_multiplier)
                v[s, ..],  s <= t;  out = concat_j(a[t, j]) Wo

Weights are made bfloat16 (vectors float32) and the reference upcasts them a
layer at a time. The tree is what ``zoo.granite_hybrid_lm`` holds::

    {"0": {"tokens": (V, d)},
     "1".."L": mamba:     {"ln1": {gamma}, "ln2": {gamma},
                           "mixer": {w_in (d, 2 H P + 2 N + H), conv_w (K, C),
                                     conv_b (C,), dt_bias, a_log, d_skip (H,),
                                     "norm": {gamma (H P,)}, w_out (H P, d)},
                           "ffn": {wg, wu (d, m), wd (m, d)}}
               attention: {"ln1": {gamma}, "ln2": {gamma},
                           "attn": {wq (d, Hq Dh), wk, wv (d, Hkv Dh),
                                    wo (Hq Dh, d)},
                           "ffn": {wg, wu, wd}},
     "L+1": {gamma}, "L+2": {}}            (the head is the embedding)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.deepseek_v3 import f32, gated, rms_norm
from benchmark.reference import (
    adam_step, dot_highest, get_dot, leaf_norms, leaf_norms_of_difference)

HIGHEST = jax.lax.Precision.HIGHEST
SEQ_BUCKET = 512      # sequences are padded to 512 x a power of two
ROW_BLOCK = 256       # positions whose logits are held at once
ROW_BLOCK_ATTN = 512  # query rows whose scores are held at once


# ---------------------------------------------------- sizes and weights


def widths(config: dict) -> dict:
    """The sizes of a configuration file under the names used here. Nothing
    is a share: every layer, head and row of the vocabulary is held."""
    a = config["assumed"]
    check = config.get("serving", {}).get("check", {})
    layer_types = tuple(config["layer_types"])
    layers = int(config["num_hidden_layers"])
    if len(layer_types) != layers or set(layer_types) - {"mamba", "attention"}:
        raise ValueError(
            f"layer_types holds {len(layer_types)} entries of "
            f"{sorted(set(layer_types))} for num_hidden_layers = {layers}")
    d, q_heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    if not config["tie_word_embeddings"] or int(config["num_local_experts"]):
        raise ValueError("this family has the embedding as its head and no "
                         "routed experts")
    return {
        **({"gap_limit": float(check["gap_limit"])}
           if "gap_limit" in check else {}),
        "vocab": int(config["vocab_size"]),
        "seq": int(config["max_position_embeddings"]),
        "layers": layers,
        "d": d,
        "layer_types": layer_types,
        "q_heads": q_heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // q_heads,
        "mlp_width": int(config["shared_intermediate_size"]),
        "ssm_heads": int(config["mamba_n_heads"]),
        "ssm_head_dim": int(config["mamba_d_head"]),
        "ssm_state": int(config["mamba_d_state"]),
        "ssm_groups": int(config["mamba_n_groups"]),
        "ssm_conv": int(config["mamba_d_conv"]),
        "ssm_expand": int(config["mamba_expand"]),
        "ssm_chunk": int(config["mamba_chunk_size"]),
        "embed_scale": float(config["embedding_multiplier"]),
        "residual_scale": float(config["residual_multiplier"]),
        "attn_scale": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "eps": float(config["rms_norm_eps"]),
        "init": float(a["initializer_range"]),
        "page": int(config.get("serving", {}).get("page_size", 16)),
    }


def _ssm_sizes(w: dict) -> tuple:
    """``(inner H P, convolution width C, W_in's columns)``."""
    inner = w["ssm_heads"] * w["ssm_head_dim"]
    conv = inner + 2 * w["ssm_groups"] * w["ssm_state"]
    return inner, conv, inner + conv + w["ssm_heads"]


def _mixer_params(w: dict) -> dict:
    inner, conv, cols = _ssm_sizes(w)
    d = w["d"]
    matrices = d * cols + inner * d
    return {"matrices": matrices,
            "rest": conv * w["ssm_conv"] + conv + 3 * w["ssm_heads"] + inner}


def _attention_params(w: dict) -> int:
    d, hd = w["d"], w["head_dim"]
    return 2 * d * w["q_heads"] * hd + 2 * d * w["kv_heads"] * hd


def param_count(w: dict) -> dict:
    d, v = w["d"], w["vocab"]
    mixer = _mixer_params(w)
    mlp = 3 * d * w["mlp_width"]
    n_ssm = sum(kind == "mamba" for kind in w["layer_types"])
    n_attn = w["layers"] - n_ssm
    mamba_layer = mixer["matrices"] + mixer["rest"] + mlp + 2 * d
    attn_layer = _attention_params(w) + mlp + 2 * d
    return {
        "mixer": mixer["matrices"] + mixer["rest"],
        "attention": _attention_params(w), "mlp": mlp,
        "mamba_layer": mamba_layer, "attention_layer": attn_layer,
        "embedding": v * d, "head": 0,  # tied: counted once
        "total": n_ssm * mamba_layer + n_attn * attn_layer + v * d + d,
    }


_SHAPE_KEYS = ("vocab", "layers", "d", "layer_types", "q_heads", "kv_heads",
               "head_dim", "mlp_width", "ssm_heads", "ssm_head_dim",
               "ssm_state", "ssm_groups", "ssm_conv", "init", "embed_scale")


def _zoo_entry():
    """The program's entry for this model; a program that has none cannot
    run the configuration, and says so before anything is computed."""
    from distkeras_tpu.models import zoo

    entry = getattr(zoo, "granite_hybrid_lm", None)
    if entry is None:
        raise RuntimeError(
            "the program has no zoo.granite_hybrid_lm: it cannot run a "
            "configuration of the granite_hybrid family")
    return entry


def make_weights(w: dict, seed):
    """Every weight from ``seed`` in one jitted call, on the default device:
    matrices bfloat16, N(0, init), the output projections (``wo``, ``w_out``,
    every ``wd``) among them: the model's own ``residual_multiplier`` (0.22,
    about 1/sqrt(20)) is its scaling of a branch by depth, and the 1/sqrt(2
    L) the other families put into these matrices would damp each branch a
    second time; the embedding N(0, init / embedding_multiplier), so that
    what enters the residual stream has the scale ``init`` gives the other
    families'. Both for one reason: the table is the head too, so a token's
    own logit stands ``sqrt(d) x |x0| / |x|`` deviations above the other
    tokens' (``x0`` the scaled embedding, ``x`` the last layer's output). At
    N(0, init) with damped branches that is 30 deviations: greedy decoding
    repeats the prompt's last token whatever the layers compute, and no
    comparison of served tokens with the reference could then fail. As made
    here it is half a deviation, and the layers decide the token. After the
    Mamba-2 reference's initialisation ``A_log = log U[1, 16]``, ``dt_bias``
    the inverse softplus of a log-uniform step in [1e-3, 1e-1], ``D`` = 1,
    convolution weights and bias U(-1/2, 1/2) (1 / sqrt(K) at K = 4, rounded
    to bfloat16 where the served tree holds them so); gains 1. ``A_log``,
    ``dt_bias``, ``D``, the convolution's bias and every gain float32."""
    _zoo_entry()
    return _make_weights(jnp.uint32(int(seed) % (2**32)),
                         **{k: w[k] for k in _SHAPE_KEYS})


@functools.partial(jax.jit, static_argnames=_SHAPE_KEYS)
def _make_weights(seed, *, vocab, layers, d, layer_types, q_heads, kv_heads,
                  head_dim, mlp_width, ssm_heads, ssm_head_dim, ssm_state,
                  ssm_groups, ssm_conv, init, embed_scale):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4 + 12 * layers))
    bf, fl = jnp.bfloat16, jnp.float32
    inner = ssm_heads * ssm_head_dim
    conv = inner + 2 * ssm_groups * ssm_state

    def normal(shape, scale=init):
        return (scale * jax.random.normal(next(keys), shape, fl)).astype(bf)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, fl, lo, hi)

    def gain(n):
        return {"gamma": jnp.ones((n,), fl)}

    out = init  # residual_multiplier is the depth scaling (make_weights)
    params = {"0": {"tokens": normal((vocab, d), init / embed_scale)}}
    for i, kind in enumerate(layer_types):
        ffn = {"wg": normal((d, mlp_width)), "wu": normal((d, mlp_width)),
               "wd": normal((mlp_width, d), out)}
        if kind == "mamba":
            step = jnp.exp(uniform((ssm_heads,), math.log(1e-3),
                                   math.log(1e-1)))
            mixer = {
                "w_in": normal((d, inner + conv + ssm_heads)),
                "conv_w": uniform((ssm_conv, conv), -0.5, 0.5).astype(bf),
                "conv_b": uniform((conv,), -0.5, 0.5),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(uniform((ssm_heads,), 1.0, 16.0)),
                "d_skip": jnp.ones((ssm_heads,), fl),
                "norm": gain(inner),
                "w_out": normal((inner, d), out),
            }
            params[str(i + 1)] = {"ln1": gain(d), "mixer": mixer,
                                  "ln2": gain(d), "ffn": ffn}
        else:
            attn = {"wq": normal((d, q_heads * head_dim)),
                    "wk": normal((d, kv_heads * head_dim)),
                    "wv": normal((d, kv_heads * head_dim)),
                    "wo": normal((q_heads * head_dim, d), out)}
            params[str(i + 1)] = {"ln1": gain(d), "attn": attn,
                                  "ln2": gain(d), "ffn": ffn}
    params[str(layers + 1)] = gain(d)
    params[str(layers + 2)] = {}
    return params


# ------------------------------------------------------------ hand-over


def build_program_model(w: dict, weights, traffic: dict):
    """The program's own model with the benchmark's seeded weights in it:
    ``zoo.granite_hybrid_lm`` built under ``jax.eval_shape`` from the
    configuration's own keys, its tree checked leaf by leaf against the
    layout above, the arrays of ``make_weights`` in its place."""
    entry = _zoo_entry()
    holder = []

    def build():
        model = entry(
            vocab_size=w["vocab"], seq_len=w["seq"], hidden_size=w["d"],
            num_attention_heads=w["q_heads"],
            num_key_value_heads=w["kv_heads"],
            shared_intermediate_size=w["mlp_width"],
            layer_types=w["layer_types"], mamba_n_heads=w["ssm_heads"],
            mamba_d_head=w["ssm_head_dim"], mamba_d_state=w["ssm_state"],
            mamba_n_groups=w["ssm_groups"], mamba_d_conv=w["ssm_conv"],
            mamba_expand=w["ssm_expand"], mamba_chunk_size=w["ssm_chunk"],
            embedding_multiplier=w["embed_scale"],
            residual_multiplier=w["residual_scale"],
            attention_multiplier=w["attn_scale"],
            logits_scaling=w["logits_scaling"], rms_norm_eps=w["eps"],
            seed=0)
        holder.append(model)
        return model.params

    want = jax.eval_shape(build)
    model = holder[0]
    if jax.tree.structure(want) != jax.tree.structure(weights) or any(
            a.shape != b.shape
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(weights))):
        raise RuntimeError(
            "the program's granite_hybrid_lm no longer has the tree that "
            "benchmark/families/granite_hybrid.py documents: the hand-over "
            "format moved")
    model.params = weights
    return model


# -------------------------------------------------------------- forward


def embed(params, tokens, w: dict):
    return w["embed_scale"] * params["0"]["tokens"][tokens].astype(jnp.float32)


def final_norm(params, x, w: dict):
    g = params[str(w["layers"] + 1)]["gamma"].astype(jnp.float32)
    return rms_norm(x, g, w["eps"])


def logits(params, h, w: dict, dot=dot_highest):
    """The embedding as the head: ``h E^T / logits_scaling``."""
    table = params["0"]["tokens"].astype(jnp.float32)
    return dot(h, table.T) / w["logits_scaling"]


def mamba_mixer(p, u, w: dict, dot):
    """The Mamba-2 mixer of one sequence, ``u`` (T, d) normalised: the
    recurrence one position after another (``lax.scan``), float32."""
    t = u.shape[0]
    nh, hp, n, k = (w["ssm_heads"], w["ssm_head_dim"], w["ssm_state"],
                    w["ssm_conv"])
    inner, conv, _ = _ssm_sizes(w)
    p = f32(p)
    zxd = dot(u, p["w_in"])
    z, raw, dt = zxd[:, :inner], zxd[:, inner:inner + conv], \
        zxd[:, inner + conv:]
    # depthwise, causal: position t sees raw[t - (K - 1) .. t], zeros before 0
    ext = jnp.concatenate([jnp.zeros((k - 1, conv), jnp.float32), raw])
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][j] * ext[j:j + t] for j in range(k)))
    x = xbc[:, :inner].reshape(t, nh, hp)
    bm, cm = xbc[:, inner:inner + n], xbc[:, inner + n:]
    step = jax.nn.softplus(dt + p["dt_bias"])  # (T, H)
    a = -jnp.exp(p["a_log"])

    def one(state, inp):
        x_t, b_t, c_t, d_t = inp
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y_t = jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)
        return state, y_t + p["d_skip"][:, None] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((nh, hp, n), jnp.float32),
                        (x, bm, cm, step))
    gated_y = y.reshape(t, inner) * jax.nn.silu(z)
    return dot(rms_norm(gated_y, p["norm"]["gamma"], w["eps"]), p["w_out"])


def attention(p, u, w: dict, dot):
    """Grouped-query attention of one sequence without any position: ``u``
    (T, d) normalised; scores times ``attention_multiplier``."""
    t = u.shape[0]
    heads, kvh, hd = w["q_heads"], w["kv_heads"], w["head_dim"]
    g = heads // kvh
    p = f32(p)
    pos = jnp.arange(t)
    q = dot(u, p["wq"]).reshape(t, heads, hd)
    k = dot(u, p["wk"]).reshape(t, kvh, hd)
    v = dot(u, p["wv"]).reshape(t, kvh, hd)

    def rows(args):
        qb, at = args
        qg = qb.reshape(qb.shape[0], kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) \
            * w["attn_scale"]
        s = jnp.where((pos[None, :] <= at[:, None])[None, None], s, -jnp.inf)
        o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST)
        return o.reshape(qb.shape[0], heads, hd)

    if t > ROW_BLOCK_ATTN and t % ROW_BLOCK_ATTN == 0:
        nb = t // ROW_BLOCK_ATTN
        o = jax.lax.map(rows, (q.reshape(nb, ROW_BLOCK_ATTN, heads, hd),
                               pos.reshape(nb, ROW_BLOCK_ATTN)))
        o = o.reshape(t, heads, hd)
    else:
        o = rows((q, pos))
    return dot(o.reshape(t, heads * hd), p["wo"])


def layer(p, x, w: dict, dot, index: int):
    """Layer ``index`` (from 0) over one sequence, (T, d) float32."""
    r = w["residual_scale"]
    u = rms_norm(x, p["ln1"]["gamma"].astype(jnp.float32), w["eps"])
    if w["layer_types"][index] == "mamba":
        x = x + r * mamba_mixer(p["mixer"], u, w, dot)
    else:
        x = x + r * attention(p["attn"], u, w, dot)
    u = rms_norm(x, p["ln2"]["gamma"].astype(jnp.float32), w["eps"])
    return x + r * gated(f32(p["ffn"]), u, dot)


def hidden(params, tokens, w: dict, dot=dot_highest):
    """The final RMSNorm's output for one sequence of token ids: (T, d)."""
    x = embed(params, tokens, w)
    for i in range(w["layers"]):
        x = layer(params[str(i + 1)], x, w, dot, i)
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
    """What a training check compares, as the other families give it, over a
    float32 copy of the seeded weights and a row at a time. For the tiny size
    of the tests: no cell trains this family (the program has no backward of
    its chunked scan that a trainer was run through, and 16 bytes a
    parameter fit no chip at a whole period)."""
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
def _layer_fn(w_items: tuple, precision: str, kind: str):
    """One layer as a program of its own (one a layer KIND: the layers of a
    kind share their shapes): the forward is called a layer at a time, so
    that one layer's weights are held upcast beside the bfloat16 tree and no
    more."""
    w = dict(w_items)
    index = w["layer_types"].index(kind)
    return jax.jit(lambda p, x: layer(p, x, w, get_dot(precision), index))


@functools.lru_cache(maxsize=None)
def _logits_fn(w_items: tuple, precision: str):
    w = dict(w_items)
    return jax.jit(lambda p, x, rows: logits(
        p, final_norm(p, x[rows], w), w, get_dot(precision)))


@functools.lru_cache(maxsize=None)
def _embed_fn(w_items: tuple):
    w = dict(w_items)
    return jax.jit(lambda p, seq: embed(p, seq, w))


def _residual(params, w: dict, seq, precision: str):
    """The last layer's output, a layer a call."""
    key = _key(w)
    x = _embed_fn(key)(params, seq)
    for i, kind in enumerate(w["layer_types"]):
        x = _layer_fn(key, precision, kind)(params[str(i + 1)], x)
    return x


def served_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """For one finished request, one full forward of the reference; at each
    served position how far the served token's logit lies below the
    reference's largest, the same for a ``control`` precision's first token,
    and the reference's OWN margin at the position, its largest logit less
    its second largest (what ``judged`` takes the positions by)."""
    key = _key(w)
    n = len(sequence)
    bucket = SEQ_BUCKET
    while bucket < n:
        bucket *= 2
    padded = np.zeros(min(w["seq"], bucket), np.int32)
    padded[:n] = sequence  # causal: what follows a position cannot reach it
    seq = jnp.asarray(padded)
    ref_x = _residual(params, w, seq, "highest")
    low_x = _residual(params, w, seq, control) if control else None
    served = np.asarray(sequence[prompt_len:], np.int64)
    positions = np.arange(prompt_len - 1, n - 1)
    gaps, control_gaps, margins = [], [], []
    for i in range(0, len(positions), ROW_BLOCK):
        pos = positions[i:i + ROW_BLOCK]
        rows = np.zeros(ROW_BLOCK, np.int32)
        rows[:len(pos)] = pos
        ref = np.asarray(
            _logits_fn(key, "highest")(params, ref_x, rows))[:len(pos)]
        top = np.partition(ref, -2, axis=-1)[:, -2:]
        best = top[:, 1]
        margins.append(best - top[:, 0])
        at = np.arange(len(pos))
        gaps.append(best - ref[at, served[i:i + ROW_BLOCK]])
        if control:
            low = np.asarray(_logits_fn(key, control)(params, low_x, rows))
            control_gaps.append(best - ref[at, low[:len(pos)].argmax(axis=-1)])
    return (np.concatenate(gaps),
            np.concatenate(control_gaps) if control else None,
            np.concatenate(margins))


def judged(gaps, w: dict, margins=None, prompt_len=None):
    """A dense model's gaps are judged as they are: nothing swaps, so one
    ``gap_limit`` holds every served token. That proves the precision of the
    weights and of the products (8-bit weights read five times the limit's
    sound runs) and NOT the precision a Mamba layer's state is held in: on
    the chip a state rounded to bfloat16 at every step turned a quarter more
    picks than the float32 one, and over the six requests of a run no
    number of the gaps and margins (the mean gap, the share of tokens that
    are not the reference's best, that share by the reference's margin)
    parts the two by less than one sample in seven misjudged on either side
    (the configuration's ``serving.check.readings``; ``controls_select.py
    --control sound --dump`` writes the gaps and margins it was read
    from). So the program offers no such state, and ``margins`` is taken
    and not used."""
    return np.asarray(gaps)


def token_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """``served_gaps`` as the serving check takes them: the harness holds
    the widest to ``gap_limit``."""
    gaps, control_gaps, _ = served_gaps(params, w, sequence, prompt_len, control)
    return judged(gaps, w), control_gaps


# --------------------------------------------------- operations and bytes
#
# Counted from the algorithm, never from the compiler's cost analysis
# (``flops.py`` says how).

STATE_BYTES = 4  # the state and the convolution's tail are float32


def decode_step(w: dict, batch: float, cached: float, *, weight_bytes: float,
                kv_bytes: float) -> dict:
    """One decode step for ``batch`` active sequences with ``cached`` tokens
    each in the cache (means over the window). Every matrix is read once and
    used for ``batch`` tokens, at 2 operations a parameter and token. A
    Mamba layer's state (``H x P x N`` float32 values a sequence) is read
    once and written once a step, its convolution tail (``(K - 1) x C``)
    too; a state value costs 5 operations (the decay's product, the outer
    product's multiply-add, the read-out's multiply-add). A cached token is
    ``2 x Hkv x Dh`` values an attention layer, read once; a token in reach
    costs ``Hq`` query heads ``Dh x 2`` operations for its score and as many
    for the weighted sum. The embedding is a lookup; the head reads the same
    table once.

    ``parts`` gives the count by part: ``ssm`` (every Mamba layer's mixer:
    its matrices, the state and the tail in and out), ``attn`` (every
    attention layer's four matrices and its cache), ``dense`` (all the gated
    MLPs) and ``head``; they sum to the whole. ``scan`` is not of a step:
    what the Mamba layers' mixers of one PREFILL CHUNK have to compute a
    real token (``flops_a_token``: the matrices, and the chunk form's
    products with the causal half of a block of ``ssm_chunk`` positions
    counted) and move a chunk whatever its length (``bytes_a_chunk``: the
    matrices, one slot's state and tail in and out). ``kernel`` is what the
    paged kernel's calls of a step have to move and compute, alone: for each
    attention layer the whole pages that hold a slot's ``cached`` positions
    (``ceil(cached / page)``, keys and values of every K/V head), the
    queries in and the outputs back in float32 at the heads' own width (the
    program hands the kernel two heads of 64 as one of 128 lanes and so
    twice these values: that is its cost, not the need), ``4 x Hq x Dh``
    operations a position in reach."""
    d, hd, kvh, heads = w["d"], w["head_dim"], w["kv_heads"], w["q_heads"]
    n_ssm = sum(kind == "mamba" for kind in w["layer_types"])
    n_attn = w["layers"] - n_ssm
    inner, conv, _ = _ssm_sizes(w)
    mixer = _mixer_params(w)
    state = inner * w["ssm_state"]  # values a layer and sequence
    tail = (w["ssm_conv"] - 1) * conv
    slot_bytes = 2 * (state + tail) * STATE_BYTES  # in and out
    ssm = {
        "flops": n_ssm * batch * (2 * mixer["matrices"] + 5 * state
                                  + 2 * w["ssm_conv"] * conv),
        "bytes": n_ssm * ((mixer["matrices"] * weight_bytes
                           + mixer["rest"] * 4) + batch * slot_bytes),
    }
    cache = n_attn * 2 * kvh * hd * cached * batch * kv_bytes
    attn = {
        "flops": n_attn * (2 * batch * _attention_params(w)
                           + batch * cached * heads * hd * 4),
        "bytes": n_attn * _attention_params(w) * weight_bytes + cache,
    }
    mlp = 3 * d * w["mlp_width"]
    dense = {"flops": w["layers"] * 2 * batch * mlp,
             "bytes": w["layers"] * mlp * weight_bytes}
    head = {"flops": 2 * batch * d * w["vocab"],
            "bytes": d * w["vocab"] * weight_bytes}
    parts = {"ssm": ssm, "attn": attn, "dense": dense, "head": head}
    total_bytes = sum(p["bytes"] for p in parts.values())
    state_bytes = n_ssm * batch * 2 * state * STATE_BYTES
    block = w["ssm_chunk"]
    pages = math.ceil(cached / w["page"])
    scan = {
        # a real token: the two matrices; C.B and the weighted sum over the
        # causal half of its block; the read of the carried state; its own
        # outer product into the next
        "flops_a_token": n_ssm * (
            2 * mixer["matrices"] + (block / 2) * 2 * w["ssm_state"]
            + (block / 2) * 2 * inner + 4 * state),
        "bytes_a_chunk": n_ssm * (mixer["matrices"] * weight_bytes
                                  + mixer["rest"] * 4 + slot_bytes),
    }
    return {
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": total_bytes,
        "weight_bytes": total_bytes - cache - n_ssm * batch * slot_bytes,
        "kv_bytes": cache, "state_bytes": state_bytes,
        "parts": parts, "scan": scan,
        "kernel": {
            "flops": n_attn * batch * cached * heads * hd * 4,
            "bytes": n_attn * batch * (
                pages * 2 * w["page"] * kvh * hd * kv_bytes
                + 2 * heads * hd * 4),
            "pages_a_slot": pages,
        },
    }
