"""The GPT-2 family: the one place in the benchmark that knows this model.

The block as published (Radford et al. 2019; Hugging Face
``GPT2LMHeadModel``): its sizes under their published keys, its weights from
the seed, its plain reference in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")`` (no kernels, no cache, no
batching tricks), the hand-over of those weights to the program's own model,
and the operations and bytes its layers need. The harness finds this file by
the ``family`` key of a configuration file (``spec.load_family``) and takes
from it the names ``README.md`` lists under "A model family" and no others.
What no model owns is imported: the precisions and their controls,
``layer_norm``, ``gelu``, Adam and the leaf norms from ``reference.py``.
This is the only file under ``benchmark/`` that imports the program's models.

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

from benchmark.reference import (
    _tree_add, adam_step, dot_highest, gelu, get_dot, layer_norm, leaf_norms,
    leaf_norms_of_difference)


# ---------------------------------------------------- sizes and weights


def widths(config: dict) -> dict:
    """The sizes of a configuration file under the names used here;
    ``vocab``, ``seq`` and ``layers`` are what every family gives, and all
    that the harness reads."""
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


# ------------------------------------------------------------ hand-over


def build_program_model(w: dict, weights, traffic: dict):
    """The program's own model with the benchmark's seeded weights in it,
    and what the mix asks for attached. ``zoo.transformer_lm`` is built
    under ``jax.eval_shape`` (its own random initialisation, leaf by leaf,
    is neither computed nor held), its tree is checked leaf by leaf against
    the layout above, and the weights made by ``make_weights`` in one jitted
    call take its place. ``attention: flash`` in the mix attaches the flash
    kernels, and every block has to take them."""
    from distkeras_tpu.models import zoo

    holder = []

    def build():
        model = zoo.transformer_lm(
            vocab_size=w["vocab"], seq_len=w["seq"], d_model=w["d"],
            num_heads=w["heads"], depth=w["layers"], seed=0)
        holder.append(model)
        return model.params

    want = jax.eval_shape(build)
    model = holder[0]
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError(
            "the program's transformer_lm no longer has the tree that "
            "benchmark/families/gpt2.py documents: the hand-over format moved")
    mlp = model.layers[1]._fc1.units if w["layers"] else w["inner"]
    if mlp != w["inner"]:
        raise RuntimeError(f"program's MLP width {mlp} != n_inner {w['inner']}")
    model.params = weights
    if traffic.get("attention") == "flash":
        from distkeras_tpu.ops.flash_attention import (
            attach_flash_attention, effective_path)

        attached = attach_flash_attention(model)
        path = effective_path(w["seq"], w["d"] // w["heads"])[0]
        if attached != w["layers"] or path != "flash":
            raise RuntimeError(f"flash attention: {attached} attached, "
                               f"effective path {path!r}")
    return model


# -------------------------------------------------------------- forward


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


# --------------------------------------------------- operations and bytes
#
# Counted from the algorithm, never from the compiler's cost analysis
# (``flops.py`` says how); each returns a dict with ``flops`` and, where a
# roofline needs it, ``bytes``.


def matmul_params(w: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    blocks' four attention matrices and two MLP matrices, and the output
    head. Embedding tables are lookups and do not count."""
    d, f = w["d"], w["inner"]
    return w["layers"] * (4 * d * d + 2 * d * f) + d * w["vocab"]


def train_flops_per_token(w: dict) -> dict:
    """Forward and backward of one token in a sequence of ``seq`` tokens:
    6 operations a matmul parameter (2 forward, 4 backward), and causal
    attention's two products (scores, values) over the half of the square
    that the mask keeps: forward 2 * 2 * d * (T / 2) a layer, three times
    that with the backward pass."""
    t, d = w["seq"], w["d"]
    dense = 6 * matmul_params(w)
    attention = w["layers"] * 3 * (2 * 2 * d * (t / 2))
    return {"flops": dense + attention, "dense": dense, "attention": attention}


def flash_attention_train(w: dict, batch: int) -> dict:
    """The flash kernels of one step over ``batch`` sequences, all layers:
    forward is two products over the causal half (scores, values); backward
    is the four the gradient needs (dp, dq, dk, dv). The scores that the
    FlashAttention-2 backward recomputes in each of its two kernels are
    recomputed work and are not counted. Bytes: q, k, v, o forward; those
    and do, dq, dk, dv backward, each (T, d) in bfloat16."""
    t, d, layers = w["seq"], w["d"], w["layers"]
    product = 2 * t * (t / 2) * d  # one (T, T/2 kept) x d product, all heads
    fwd = 2 * product
    bwd = 4 * product
    return {
        "flops_fwd": batch * layers * fwd, "flops_bwd": batch * layers * bwd,
        "flops": batch * layers * (fwd + bwd),
        "bytes_fwd": batch * layers * 4 * t * d * 2,
        "bytes_bwd": batch * layers * 8 * t * d * 2,
    }


def decode_step(w: dict, batch: float, cached: float, *, weight_bytes: float,
                kv_bytes: float) -> dict:
    """One decode step for ``batch`` active sequences with ``cached`` tokens
    each in the cache (means over the window): every matmul weight is read
    once and used for ``batch`` tokens; every cached key and value is read
    once. ``weight_bytes`` is bytes a matmul weight as served (1 for int8),
    ``kv_bytes`` bytes a cached value."""
    d, layers = w["d"], w["layers"]
    n = matmul_params(w)
    flops = 2 * n * batch + layers * 2 * 2 * d * cached * batch
    kv = layers * 2 * d * cached * batch * kv_bytes
    weights = n * weight_bytes
    return {"flops": flops, "bytes": weights + kv,
            "weight_bytes": weights, "kv_bytes": kv}
