"""The plain reference: the GPT-2 block as published (Radford et al. 2019;
Hugging Face ``GPT2LMHeadModel``), in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks; it imports nothing of ``distkeras_tpu``.

Weights are made here from the seed, in one jitted call, and handed to the
program in the tree layout below (the hand-over format: what the program's
``zoo.transformer_lm`` holds). Departures from the published model, each
listed in the configuration files under ``assumed``: no bias on q/k/v, an
untied output head with a bias, tanh GELU, dropout 0.

    {"0": {"tokens": (V, d), "positions": (T, d)},
     "1".."L": {"ln1": {gamma, beta}, "mhsa": {wq, wk, wv, wo, bo},
                "ln2": {gamma, beta}, "fc1": {kernel, bias},
                "fc2": {kernel, bias}},
     "L+1": {gamma, beta}, "L+2": {"kernel": (d, V), "bias": (V,)}}
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
ADAM = {"b1": 0.9, "b2": 0.999, "eps": 1e-8}


def widths(config: dict) -> dict:
    """The sizes of a configuration file under the names used here."""
    return {
        "vocab": int(config["vocab_size"]), "seq": int(config["n_positions"]),
        "d": int(config["n_embd"]), "heads": int(config["n_head"]),
        "inner": int(config["n_inner"]), "layers": int(config["n_layer"]),
        "init": float(config.get("initializer_range", 0.02)),
        "gelu": config["assumed"]["gelu"],
    }


def param_count(w: dict) -> dict:
    d, v, t, f = w["d"], w["vocab"], w["seq"], w["inner"]
    block_matmul = 4 * d * d + 2 * d * f
    block = block_matmul + d + f + d + 4 * d  # bo, fc biases, two LayerNorms
    return {
        "block": block, "block_matmul": block_matmul,
        "embedding": v * d + t * d, "head": d * v + v, "final_ln": 2 * d,
        "total": v * d + t * d + w["layers"] * block + 2 * d + d * v + v,
        "matmul": w["layers"] * block_matmul + d * v,
    }


def make_weights(w: dict, seed):
    """Every weight from ``seed`` in one jitted call, on the default device:
    N(0, init) as the published initializer_range says, residual output
    projections scaled by 1/sqrt(2 L) (GPT-2), biases 0, LayerNorm 1/0."""
    return _make_weights(
        jnp.uint32(int(seed) % (2**32)), **{k: w[k] for k in (
            "vocab", "seq", "d", "inner", "layers", "init")})


@functools.partial(jax.jit, static_argnames=(
    "vocab", "seq", "d", "inner", "layers", "init"))
def _make_weights(seed, *, vocab, seq, d, inner, layers, init):
    key = jax.random.PRNGKey(seed)
    keys = iter(jax.random.split(key, 3 + 6 * layers))

    def normal(shape, scale=1.0):
        return init * scale * jax.random.normal(next(keys), shape, jnp.float32)

    def ln():
        return {"gamma": jnp.ones((d,), jnp.float32),
                "beta": jnp.zeros((d,), jnp.float32)}

    out_scale = 1.0 / math.sqrt(2 * layers)
    params = {"0": {"tokens": normal((vocab, d)), "positions": normal((seq, d))}}
    for i in range(1, layers + 1):
        params[str(i)] = {
            "ln1": ln(),
            "mhsa": {"wq": normal((d, d)), "wk": normal((d, d)),
                     "wv": normal((d, d)), "wo": normal((d, d), out_scale),
                     "bo": jnp.zeros((d,), jnp.float32)},
            "ln2": ln(),
            "fc1": {"kernel": normal((d, inner)),
                    "bias": jnp.zeros((inner,), jnp.float32)},
            "fc2": {"kernel": normal((inner, d), out_scale),
                    "bias": jnp.zeros((d,), jnp.float32)},
        }
    params[str(layers + 1)] = ln()
    params[str(layers + 2)] = {"kernel": normal((d, vocab)),
                               "bias": jnp.zeros((vocab,), jnp.float32)}
    return params


# ----------------------------------------------------------- precisions
#
# ``dot`` is the one place the precision of a matrix product is decided.
# "highest" is the reference; the others are the contract's controls: the
# reference computed one step below what the configuration states.


def dot_highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _fake_quant(x, levels: float):
    """Symmetric per-tensor rounding to ``levels`` steps each side, with a
    straight-through gradient (as quantized training does it)."""
    scale = jnp.max(jnp.abs(x)) / levels
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -levels, levels) * scale
    return x + jax.lax.stop_gradient(q - x)


def make_dot_int(levels: float):
    """Products with both operands rounded to ``levels`` steps, and in the
    backward pass the incoming gradient too: int8 is 127, int4 is 7."""

    @jax.custom_vjp
    def dot(a, b):
        return dot_highest(_fake_quant(a, levels), _fake_quant(b, levels))

    def fwd(a, b):
        return dot(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        gq = _fake_quant(g, levels)
        aq, bq = _fake_quant(a, levels), _fake_quant(b, levels)
        ga = dot_highest(gq, jnp.swapaxes(bq, -1, -2))
        gb = dot_highest(jnp.swapaxes(aq, -1, -2), gq)
        if gb.ndim > b.ndim:  # a batch of rows against one matrix
            gb = gb.sum(axis=tuple(range(gb.ndim - b.ndim)))
        return ga.astype(a.dtype), gb.astype(b.dtype)

    dot.defvjp(fwd, bwd)
    return dot


def dot_weights_int(levels: float):
    """Weight-only rounding, per output column (what a w8/w4 server holds):
    the activations stay float32."""

    def dot(a, b):
        scale = jnp.max(jnp.abs(b), axis=0, keepdims=True) / levels
        scale = jnp.where(scale == 0, 1.0, scale)
        bq = jnp.clip(jnp.round(b / scale), -levels, levels) * scale
        return dot_highest(a, bq)

    return dot


def get_dot(precision: str):
    return {
        "highest": dot_highest,
        "int8": make_dot_int(127.0),
        "w_int4": dot_weights_int(7.0),
    }[precision]


# -------------------------------------------------------------- forward


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["gamma"] + p["beta"]


def gelu(x, flavour: str):
    if flavour == "tanh":
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x**3)))
    if flavour == "erf":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown gelu flavour {flavour!r}")


def block(p, x, heads: int, flavour: str, dot):
    """x + Attn(LN(x)), then x + MLP(LN(x)); x is (T, d), one sequence."""
    t, d = x.shape
    hd = d // heads
    h = layer_norm(x, p["ln1"])
    q = dot(h, p["mhsa"]["wq"]).reshape(t, heads, hd)
    k = dot(h, p["mhsa"]["wk"]).reshape(t, heads, hd)
    v = dot(h, p["mhsa"]["wv"]).reshape(t, heads, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(t, d)
    x = x + dot(o, p["mhsa"]["wo"]) + p["mhsa"]["bo"]
    h = layer_norm(x, p["ln2"])
    h = gelu(dot(h, p["fc1"]["kernel"]) + p["fc1"]["bias"], flavour)
    return x + dot(h, p["fc2"]["kernel"]) + p["fc2"]["bias"]


def hidden(params, tokens, w: dict, dot=dot_highest, remat: bool = False):
    """The final LayerNorm's output for one sequence of token ids: (T, d)."""
    t = tokens.shape[0]
    x = params["0"]["tokens"][tokens] + params["0"]["positions"][:t]
    blk = functools.partial(block, heads=w["heads"], flavour=w["gelu"], dot=dot)
    if remat:
        blk = jax.checkpoint(blk)
    for i in range(1, w["layers"] + 1):
        x = blk(params[str(i)], x)
    return layer_norm(x, params[str(w["layers"] + 1)])


def logits(params, h, w: dict, dot=dot_highest):
    head = params[str(w["layers"] + 2)]
    return dot(h, head["kernel"]) + head["bias"]


def sequence_loss(params, tokens, w: dict, dot=dot_highest):
    """Mean next-token cross-entropy of one sequence (T - 1 predictions)."""
    h = hidden(params, tokens, w, dot, remat=True)
    lg = logits(params, h[:-1], w, dot)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


# ------------------------------------------------------------- training


def _key(w: dict) -> tuple:
    """The widths as a hashable key of the cached jitted functions."""
    return tuple(sorted(w.items()))


def batch_grads(params, batch, w: dict, precision: str = "highest"):
    """Loss and gradients of one batch (B, T), a row at a time so that the
    reference fits beside nothing else: mean over rows of the row loss."""
    fn = _row_grad_fn(_key(w), precision)
    loss, grads = None, None
    for row in np.asarray(batch):
        l, g = fn(params, jnp.asarray(row, jnp.int32))
        loss = l if loss is None else loss + l
        grads = g if grads is None else _tree_add(grads, g)
    n = float(len(batch))
    return loss / n, jax.tree.map(lambda x: x / n, grads)


@functools.lru_cache(maxsize=None)
def _row_grad_fn(w_items: tuple, precision: str):
    w = dict(w_items)
    dot = get_dot(precision)
    return jax.jit(jax.value_and_grad(
        lambda p, row: sequence_loss(p, row, w, dot)))


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnames=("lr",), donate_argnums=(0, 2, 3))
def adam_step(params, grads, mu, nu, count, *, lr: float):
    """optax.adam's update with its defaults, written out."""
    b1, b2, eps = ADAM["b1"], ADAM["b2"], ADAM["eps"]
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu, count


@jax.jit
def leaf_norms(tree):
    """The L2 norm of every leaf, as one vector in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_norms_of_difference(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def train_readings(w: dict, seed, batches, lr: float,
                   precision: str = "highest", moment_after: int = 1) -> dict:
    """What the training check compares, computed by the reference: the loss
    of each of the first steps, the norm of every leaf of Adam's first
    moment after ``moment_after`` steps (after one step that is the first
    gradient times 1 - b1), and of the parameters' change after the last
    step."""
    params = make_weights(w, seed)
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
    change = np.asarray(leaf_norms_of_difference(params, make_weights(w, seed)))
    return {"losses": losses, "moment_norms": moment_norms,
            "change_norms": change}


# -------------------------------------------------------------- serving


SEQ_BUCKET = 512   # sequences are padded to a multiple: few compiled shapes
ROW_BLOCK = 256    # positions whose logits are held at once


def token_gaps(params, w: dict, sequence, prompt_len: int, control=None):
    """For one finished request (prompt + served tokens), one full forward
    of the reference: at each served position, how far the served token's
    logit lies below the reference's largest. With ``control`` (a precision
    name) also the same gap for the token which that precision puts first
    at each position of the same prompt and tokens."""
    key = _key(w)
    n = len(sequence)
    padded = np.zeros(min(w["seq"], -(-n // SEQ_BUCKET) * SEQ_BUCKET), np.int32)
    padded[:n] = sequence  # causal: what follows a position cannot reach it
    seq = jnp.asarray(padded)
    ref_h = _hidden_fn(key, "highest")(params, seq)
    low_h = _hidden_fn(key, control)(params, seq) if control else None
    served = np.asarray(sequence[prompt_len:], np.int64)
    positions = np.arange(prompt_len - 1, n - 1)
    gaps, control_gaps = [], []
    for i in range(0, len(positions), ROW_BLOCK):
        pos = positions[i:i + ROW_BLOCK]
        rows = np.zeros(ROW_BLOCK, np.int32)
        rows[:len(pos)] = pos
        ref = np.asarray(_logits_fn(key, "highest")(params, ref_h, rows))[:len(pos)]
        best = ref.max(axis=-1)
        at = np.arange(len(pos))
        gaps.append(best - ref[at, served[i:i + ROW_BLOCK]])
        if control:
            low = np.asarray(_logits_fn(key, control)(params, low_h, rows))
            control_gaps.append(best - ref[at, low[:len(pos)].argmax(axis=-1)])
    return (np.concatenate(gaps),
            np.concatenate(control_gaps) if control else None)


@functools.lru_cache(maxsize=None)
def _hidden_fn(w_items: tuple, precision: str):
    w = dict(w_items)
    return jax.jit(lambda p, seq: hidden(p, seq, w, get_dot(precision)))


@functools.lru_cache(maxsize=None)
def _logits_fn(w_items: tuple, precision: str):
    w = dict(w_items)
    return jax.jit(lambda p, h, rows: logits(p, h[rows], w, get_dot(precision)))
