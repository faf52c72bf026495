"""The blocks of latent attentions and routed experts, each written once:
``LatentMoEBlock`` (the ``deepseek_v3`` model type: one attention, one
FFN) and ``ShortcutMoEBlock`` (the LongCat-Flash layer: two attentions
and two dense MLPs beside a shortcut-connected expert layer with
zero-compute experts). They share every function of the arithmetic below.

One function, a block's ``forward``, is the layer's arithmetic for all
three of its uses:

- ``apply`` (``Sequential.apply``, ``eval_shape``, training-side code and
  the CPU tests): the full causal forward, attention in its *expanded*
  form over the sequence's own latents;
- the serving engine's prefill-chunk program: a chunk of one sequence's
  tokens against that slot's gathered latent row, expanded attention;
- the serving engine's decode-step program: one token a slot against the
  slot's latent pages, attention in its *absorbed* form.

What differs between them is who holds the cache: ``forward`` hands the
new latent rows of its tokens to ``exchange`` and attends over what that
returns. ``apply`` passes the identity; the stepper's closures pass a
function that scatters the rows into the page pool and returns the slot's
gathered pages, or (the decode step, ``ops/paged_attention.py``) a
callable that attends the pages where they lie (page and slot bookkeeping
stay in ``serving/engine.py``).

``LatentMoEBlock``: ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``; FFN is a gated SiLU MLP, or an expert layer (sigmoid
scores over all routed experts, top-k of score + selection bias, chosen
scores normalised and scaled; plus shared experts on every token).
``ShortcutMoEBlock``: its docstring. The cache holds, a token and an
attention, the normalised latent ``cn`` (``kv_rank``) and the rotated
shared key ``k_pe`` (``rope_dim``), and nothing else; a block says how
many such rows a token it caches (``cached_rows``) and that its kind is
``"latent"``: the serving engine asks those, never the class.

Matrix products take their operands in the weights' dtype (float32 as
initialised, bfloat16 as served) and accumulate in float32; norms,
softmax, the router and the residual stream are float32.
"""

from __future__ import annotations

import typing

import numpy as np

import jax
import jax.numpy as jnp

from distkeras_tpu.models.layers import Layer, register_layer
from distkeras_tpu.ops.grouped_matmul import (
    grouped_form,
    grouped_matmul,
    plain_grouped_matmul,
)


class BlockUnsupportedError(NotImplementedError):
    """A serving feature that a block which caches latent rows cannot run yet
    (the dense slot bank, speculation, fork/beam, ``tp`` meshes, K/V
    export, swap-out). Not a ``ValueError``: ``ServingEngine`` demotes a
    model whose stepper raises one to predict-only, and a refused feature
    must fail the boot instead."""


class Picks(typing.NamedTuple):
    """What an expert layer's tokens chose, for the step's counters:
    ``sizes`` ``(E_held,)`` tokens on each held routed expert, ``zero``
    how many picks took an identity (zero-compute) expert, ``overflow``
    how many of the layer's calls held more rows than one pass of
    ``routed_experts`` takes (None: the layer is not compacted)."""

    sizes: jax.Array
    zero: jax.Array | int
    overflow: jax.Array | None = None


# --------------------------------------------------------------- arithmetic


def _operands(a, b):
    """Both operands of a product in ``b``'s dtype. XLA's CPU backend has
    no bfloat16 x bfloat16 -> float32 product for every shape: there (the
    tests) bfloat16 operands are upcast, which gives the same numbers,
    since a product of two bfloat16 values is exact in float32."""
    a = a.astype(b.dtype)
    if b.dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        return a.astype(jnp.float32), b.astype(jnp.float32)
    return a, b


def _einsum(spec, a, b):
    """``einsum`` with operands in ``b``'s dtype (a weight's, or the cached
    latents'), accumulated in float32."""
    return jnp.einsum(spec, *_operands(a, b),
                      preferred_element_type=jnp.float32)


def matmul(x, w):
    """``x @ w`` for activations and a weight matrix, in the weight's dtype,
    float32 out."""
    return _einsum("...k,km->...m", x, w)


def _grouped_mm(x, w, sizes):
    """Rows of ``x`` sorted by group against the stacked ``(G, k, n)``
    weights, each group over its own rows, in the weights' dtype, float32
    out, the rows past the last group zero. Which form multiplies is read
    off the widths (``ops.grouped_matmul.grouped_form``): the kernel
    ``grouped_matmul`` where ``k`` and ``n`` are whole groups of 128 lanes
    (compiled on a TPU, interpreted elsewhere), ``jax.lax.ragged_dot``
    otherwise (the tests' widths of 16-64)."""
    kernel = grouped_form(*w.shape[1:]) == "kernel"
    return (grouped_matmul if kernel else plain_grouped_matmul)(
        *_operands(x, w), sizes)


def _grouped_mlp(p, xs, sizes):
    """``gated_mlp`` over rows sorted by expert, each group of ``sizes``
    rows through its own expert of the stacked weights ``p``."""
    h = jax.nn.silu(_grouped_mm(xs, p["wg"], sizes)) * _grouped_mm(
        xs, p["wu"], sizes)
    return _grouped_mm(h, p["wd"], sizes)


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * gamma.astype(jnp.float32)


def rope(x, pos, theta, freq=None, factor=1.0):
    """Rotate the pairs ``(2i, 2i+1)`` of the last axis by ``pos *
    theta^(-2i/n)`` (the interleaved pairing; ``n`` the axis' size).
    ``pos`` broadcasts against ``x``'s leading axes. ``freq`` ``(n/2,)``
    gives the pairs' frequencies in ``theta``'s place (scaled rotary
    positions blend them), ``factor`` multiplies cosine and sine."""
    n = x.shape[-1]
    if freq is None:
        freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x = x.astype(jnp.float32)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1
    ).reshape(x.shape)


def gated_mlp(p, x):
    """``(silu(x Wg) * (x Wu)) Wd``."""
    return matmul(jax.nn.silu(matmul(x, p["wg"])) * matmul(x, p["wu"]), p["wd"])


def route(p, x, top_k, scale, softmax=False, normalise=None):
    """Scores over ALL the router's outputs in float32 (sigmoid, or with
    ``softmax`` a softmax over the outputs); the top ``k`` of score +
    selection bias (a router without a ``bias`` selects by score);
    weights = the chosen scores times ``scale``, with ``normalise``
    first divided by their sum (None: sigmoid scores are, softmax
    scores are not, the two forms the latent blocks use).
    Returns ``(chosen (n, k) int32, weights (n, k) f32)``."""
    if normalise is None:
        normalise = not softmax
    with jax.named_scope("moe/route"):
        logits = jnp.dot(
            x.astype(jnp.float32), p["wr"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        s = jax.nn.softmax(logits, axis=-1) if softmax else jax.nn.sigmoid(
            logits)
        biased = s + p["bias"].astype(jnp.float32) if "bias" in p else s
        _, chosen = jax.lax.top_k(biased, top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if normalise:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w * scale


def held_capacity(rows, e_held, n_outputs):
    """Rows one pass of ``routed_experts`` takes, from what it can read
    off its arguments: of ``rows`` (token, pick) pairs a share of
    ``e_held / n_outputs`` belong to a held expert under even routing;
    a quarter more and 128 rows of room, in tiles of 128 rows (the row
    tile of ``ops.grouped_matmul``, which multiplies a whole tile for
    every expert that has a row in it), and an odd number of them. The
    odd number is history (PR 36): XLA's ``ragged_dot`` kernel takes the
    largest power of two that divides its rows (up to 512) as its row
    tile, so an odd count of 128-row tiles kept an expert's few rows bound
    by its weights' bytes where a tile of 512 is bound by the tile's
    products; the kernel's tile is 128 whatever the rows (PR 43), the
    widths that keep ``ragged_dot`` still gain by it, and the count stays
    as the programs and their tests have it. None where the pass would be
    more than half of ``rows`` (every expert held, a handful of tokens, a
    quarter of the experts under a decode step's rows): compacting saves
    less there than its own sort and scatter cost."""
    tiles = -(-int(1.25 * rows * e_held / n_outputs + 128) // 128)
    cap = 128 * (tiles | 1)
    return cap if 2 * cap <= rows else None


def routed_experts(p, x, chosen, weights, held, n_experts, token_mask=None):
    """The held experts' part of the routed output, no token dropped:
    the (token, choice) pairs are sorted by expert and the three products
    run as grouped products (``_grouped_mm``) over the stacked
    ``(E_held, in, out)`` weights, each expert over exactly the rows
    routed to it. A pair whose expert is not held here (or whose token
    ``token_mask`` switches off) adds nothing and costs no product.

    Where this chip holds a share of the experts, it costs no row
    either. The stable sort puts the held pairs first, so only the first
    ``cap`` sorted pairs (``held_capacity``: a function of the shapes and
    of ``len(held)``, nothing a caller sets) are gathered, multiplied and
    weighted, and each row is added to its token's sum. Routing that
    holds more than ``cap`` pairs here (skew, a batch that picks held
    experts alone) is never cut: the pairs past ``cap`` go through the
    same pass, ``cap`` at a time, in a loop that makes no trip under even
    routing, and ``Picks.overflow`` counts the calls that needed it. Where
    a pass would be more than half the rows (every expert held, a few
    tokens) ``cap`` is None and all rows go at once, through the gather
    back over every pair.

    Returns ``(y (n, d) f32, Picks)``; ``Picks.sizes`` ``(E_held,)`` int32
    are the group sizes."""
    n, k = chosen.shape
    e_held = p["wg"].shape[0]
    local = np.full((n_experts + 1,), e_held, np.int32)  # sentinel: absent
    local[np.asarray(held, np.int64)] = np.arange(e_held, dtype=np.int32)
    cap = held_capacity(n * k, e_held, n_experts)
    with jax.named_scope("moe/experts"):
        flat = jnp.asarray(local)[chosen.reshape(-1)]  # (n k,) local ids
        if token_mask is not None:
            flat = jnp.where(jnp.repeat(token_mask, k), flat, e_held)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=e_held + 1)[:e_held].astype(
            jnp.int32
        )
        if cap is None:
            y = _grouped_mlp(p, x[order // k], sizes)  # (n k, d), sorted
            # rows past the held groups belong to no expert: weight 0
            wsorted = jnp.where(
                flat[order] < e_held, weights.reshape(-1)[order], 0.0
            )
            y = jnp.where(wsorted[:, None] != 0.0, y * wsorted[:, None], 0.0)
            back = jnp.argsort(order)  # sorted row of each (token, choice)
            return y[back].reshape(n, k, -1).sum(axis=1), Picks(sizes, 0)

        ends = jnp.cumsum(sizes)
        n_held = ends[-1]
        # whole passes: a slice past the last pair reads pairs of weight 0
        order = jnp.pad(order, (0, -(n * k) % cap))
        wflat = weights.reshape(-1)

        def add_pass(lo, out):
            """Sorted pairs ``lo .. lo + cap``: each expert's rows among
            them through its three products, added to their tokens."""
            pairs = jax.lax.dynamic_slice_in_dim(order, lo, cap)
            span = jnp.clip(ends, lo, lo + cap) - jnp.clip(
                ends - sizes, lo, lo + cap)
            y = _grouped_mlp(p, x[pairs // k], span)  # (cap, d), sorted
            # rows past the held pairs belong to no expert: weight 0
            w = jnp.where(lo + jnp.arange(cap) < n_held, wflat[pairs], 0.0)
            y = jnp.where(w[:, None] != 0.0, y * w[:, None], 0.0)
            return out.at[pairs // k].add(y)

        out = add_pass(0, jnp.zeros((n, x.shape[-1]), jnp.float32))
        out = jax.lax.fori_loop(
            1, (n_held + cap - 1) // cap,
            lambda j, out: add_pass(j * cap, out), out)
        return out, Picks(sizes, 0, (n_held > cap).astype(jnp.int32))


def attend_expanded(p, q, latent, mask, nh, nope, vd, n_keys=None,
                    key_block=512):
    """Attention with keys and values expanded from the latents: ``q``
    ``(B, n, H, nope+rope)``, ``latent`` ``(B, t, rank+rope)``, ``mask``
    ``(B|1, n, t)``.

    ``n_keys`` (a traced scalar; the prefill chunk's form): only the first
    ``n_keys`` cache positions can be attended, and the work is done for
    those alone: key blocks of ``key_block`` positions, as many as hold
    ``n_keys``, each expanded from its latents and folded into a running
    softmax. Without it (``apply``): every key at once, differentiable."""
    b, n = q.shape[:2]
    t = latent.shape[1]
    rank = p["wkvb"].shape[0]
    cd = p["wkvb"].dtype
    scale = 1.0 / np.sqrt(q.shape[-1])

    def expand(lat):
        kv = matmul(lat[..., :rank], p["wkvb"]).reshape(
            b, lat.shape[1], nh, nope + vd)
        k_pe = jnp.broadcast_to(
            lat[:, :, None, rank:].astype(jnp.float32),
            (b, lat.shape[1], nh, lat.shape[-1] - rank),
        )
        k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1).astype(cd)
        return k, kv[..., nope:].astype(cd)

    if n_keys is None or t <= key_block or t % key_block:
        k, v = expand(latent)
        s = _einsum("bnhd,bthd->bhnt", q, k) * scale
        s = jnp.where(mask[:, None], s, -jnp.inf)
        o = _einsum("bhnt,bthd->bnhd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(b, n, nh * vd)

    def fold(j, carry):
        m, l, acc = carry  # running max, sum and weighted values
        at = j * key_block
        k, v = expand(jax.lax.dynamic_slice_in_dim(latent, at, key_block, 1))
        mb = jax.lax.dynamic_slice_in_dim(mask, at, key_block, 2)
        s = _einsum("bnhd,bthd->bhnt", q, k) * scale
        s = jnp.where(mb[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # a row with no key yet keeps m = -inf: exp(-inf - 0) = 0, no NaN
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        w = jnp.exp(s - safe[..., None])
        fix = jnp.exp(m - safe)
        l = l * fix + w.sum(axis=-1)
        acc = acc * fix.transpose(0, 2, 1)[..., None] + _einsum(
            "bhnt,bthd->bnhd", w, v)
        return m_new, l, acc

    blocks = jnp.minimum((n_keys + key_block - 1) // key_block,
                         t // key_block)
    m, l, acc = jax.lax.fori_loop(0, blocks, fold, (
        jnp.full((b, nh, n), -jnp.inf, jnp.float32),
        jnp.zeros((b, nh, n), jnp.float32),
        jnp.zeros((b, n, nh, vd), jnp.float32),
    ))
    o = acc / l.transpose(0, 2, 1)[..., None]
    return o.reshape(b, n, nh * vd)


def attend_absorbed(p, q, latent, mask, nh, nope, vd):
    """The same attention with ``Wkvb`` absorbed into the query and the
    output: scores ``([q_nope Wuk^T | q_pe] . [cn | k_pe]) / sqrt(dq)``,
    ``o = (sum w cn) Wuv``. ``latent`` is the cached rows ``(B, t,
    rank+rope)``, read as they are cached (bfloat16 as served) under
    ``mask`` and accumulated in float32; or, where the cache's holder
    attends its rows where they lie, a callable ``(qc (B, n, H,
    rank+rope) f32, scale) -> (B, n, H, rank) f32`` that is the softmax
    and both products (``mask`` is then its own business)."""
    b, n = q.shape[:2]
    rank = p["wkvb"].shape[0]
    wkvb = p["wkvb"].reshape(rank, nh, nope + vd)
    wuk, wuv = wkvb[..., :nope], wkvb[..., nope:]
    q_lat = _einsum("bnhd,chd->bnhc", q[..., :nope], wuk)
    qc = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)
    if callable(latent):
        o_lat = latent(qc, 1.0 / np.sqrt(q.shape[-1]))
    else:
        s = _einsum("bnhc,btc->bhnt", qc, latent) / np.sqrt(q.shape[-1])
        s = jnp.where(mask[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o_lat = _einsum("bhnt,btc->bnhc", w, latent[..., :rank])
    o = _einsum("bnhc,chv->bnhv", o_lat, wuv)
    return o.reshape(b, n, nh * vd)


def latent_attention(blk, p, x, pos, mask, exchange=None, absorbed=False,
                     n_keys=None):
    """``x + Attn(RMSNorm(x))`` for one latent attention of ``blk`` (its
    sizes, ``rope_theta``, ``epsilon``) with the parameters ``p`` =
    ``{"ln1", "attn"}``; ``x`` ``(B, n, d)`` float32; the other arguments
    as ``LatentMoEBlock.forward`` has them. The query is one projection
    (``wq``) or low-rank (``wqa`` -> RMSNorm ``q_norm`` -> ``wqb``); with
    ``blk.scale_q`` / ``blk.scale_kv`` the query is scaled by ``sqrt(d /
    q_rank)`` after ``wqb`` and the normalised latent by ``sqrt(d /
    kv_rank)`` before ``wkvb``, so the cached row is the scaled one."""
    a = p["attn"]
    b, n, d = x.shape
    nh, nope, vd = blk.num_heads, blk.qk_nope_dim, blk.v_dim
    with jax.named_scope("mla"):
        h = rms_norm(x, p["ln1"]["gamma"], blk.epsilon)
        if "wqa" in a:
            cq = rms_norm(matmul(h, a["wqa"]), a["q_norm"]["gamma"],
                          blk.epsilon)
            q = matmul(cq, a["wqb"])
            if blk.scale_q:
                q = q * np.sqrt(d / a["wqa"].shape[-1])
        else:
            q = matmul(h, a["wq"])
        q = q.reshape(b, n, nh, nope + blk.qk_rope_dim)
        q = jnp.concatenate([
            q[..., :nope],
            rope(q[..., nope:], pos[..., None], blk.rope_theta),
        ], axis=-1)
        ckv = matmul(h, a["wkva"])
        cn = rms_norm(ckv[..., :blk.kv_rank], a["kv_norm"]["gamma"],
                      blk.epsilon)
        if blk.scale_kv:
            cn = cn * np.sqrt(d / blk.kv_rank)
        new = jnp.concatenate([
            cn, rope(ckv[..., blk.kv_rank:], pos, blk.rope_theta),
        ], axis=-1)
        latent = new if exchange is None else exchange(new)
        if absorbed:
            o = attend_absorbed(a, q, latent, mask, nh, nope, vd)
        else:
            o = attend_expanded(a, q, latent, mask, nh, nope, vd, n_keys,
                                blk.key_block)
        return x + matmul(o, a["wo"])


# -------------------------------------------------------------------- layers


def _normal(rng, shape, std, dtype):
    return (std * jax.random.normal(rng, shape, jnp.float32)).astype(dtype)


@register_layer
class RMSNorm(Layer):
    """``x / sqrt(mean(x^2) + eps) * gamma``, in float32."""

    def __init__(self, epsilon=1e-6):
        self.epsilon = float(epsilon)

    def init(self, rng, in_shape):
        return {"gamma": jnp.ones((in_shape[-1],), jnp.float32)}, {}, in_shape

    def apply(self, params, state, x, train=False, rng=None):
        return rms_norm(x, params["gamma"], self.epsilon), state

    def get_config(self):
        return {"layer": "RMSNorm", "epsilon": self.epsilon}


class _LatentBlock(Layer):
    """What the blocks of latent attentions share: the kind the serving
    engine reads (never the class), the held experts, ``apply``."""

    kind = "latent"
    causal = True
    cached_rows = 1  # latent rows a token caches: one an attention
    scale_q = scale_kv = False  # see ``latent_attention``
    key_block = 512  # cache positions a prefill chunk attends at once

    @property
    def held(self) -> list:
        if self.experts_held is None:
            return list(range(self.n_experts))
        return self.experts_held

    @property
    def latent_width(self) -> int:
        """Values an attention caches a token: the latent and the shared
        rotary key."""
        return self.kv_rank + self.qk_rope_dim

    def _check_experts(self, n_outputs):
        held = self.held
        if not 1 <= self.top_k <= n_outputs or (
                len(set(held)) != len(held)
                or not all(0 <= e < self.n_experts for e in held)):
            raise ValueError(
                f"expert layer: top_k {self.top_k} of {n_outputs} router "
                f"outputs, {self.n_experts} routed experts, held {held}"
            )

    def apply(self, params, state, x, train=False, rng=None):
        b, n, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(n), (b, n))
        mask = jnp.tril(jnp.ones((n, n), bool))[None]
        y, _ = self.forward(params, x, pos, mask)
        return y, state

    # -- parameters: N(0, 0.02), output projections scaled by out_scale -----

    _std = 0.02

    def _mlp_init(self, ks, d, width, lead=()):
        std, dt = self._std, jnp.float32
        return {"wg": _normal(next(ks), (*lead, d, width), std, dt),
                "wu": _normal(next(ks), (*lead, d, width), std, dt),
                "wd": _normal(next(ks), (*lead, width, d),
                              std * self.out_scale, dt)}

    def _router_init(self, ks, d, n_outputs):
        return {"wr": _normal(next(ks), (d, n_outputs), self._std,
                              jnp.float32),
                "bias": jnp.zeros((n_outputs,), jnp.float32)}

    def _attention_init(self, ks, d, q_rank=None):
        """One latent attention's ``{"ln1", "attn"}`` and the ``ln2`` that
        follows it; the query one projection, or low-rank with ``q_rank``."""
        std, dt = self._std, jnp.float32
        nh, dq = self.num_heads, self.qk_nope_dim + self.qk_rope_dim
        if q_rank is None:
            query = {"wq": _normal(next(ks), (d, nh * dq), std, dt)}
        else:
            query = {"wqa": _normal(next(ks), (d, q_rank), std, dt),
                     "q_norm": {"gamma": jnp.ones((q_rank,), dt)},
                     "wqb": _normal(next(ks), (q_rank, nh * dq), std, dt)}
        return {
            "ln1": {"gamma": jnp.ones((d,), dt)},
            "attn": {
                **query,
                "wkva": _normal(next(ks), (d, self.latent_width), std, dt),
                "kv_norm": {"gamma": jnp.ones((self.kv_rank,), dt)},
                "wkvb": _normal(
                    next(ks),
                    (self.kv_rank, nh * (self.qk_nope_dim + self.v_dim)),
                    std, dt),
                "wo": _normal(next(ks), (nh * self.v_dim, d),
                              std * self.out_scale, dt),
            },
            "ln2": {"gamma": jnp.ones((d,), dt)},
        }


@register_layer
class LatentMoEBlock(_LatentBlock):
    """One pre-RMSNorm block of latent attention and a gated MLP
    (``n_experts=0``: width ``ffn_width``) or an expert layer
    (``n_experts`` routed experts of width ``expert_width``, ``top_k`` a
    token, ``n_shared`` shared experts fused into one MLP of ``n_shared *
    expert_width``).

    ``experts_held``: the routed experts this layer holds (ids; None =
    all). The router keeps its full width and its ``top_k``; the layer
    computes the held experts' part of the routed sum, plus the shared
    experts. The stacked expert weights are ``(len(held), d, width)`` x2
    and ``(len(held), width, d)``. Nothing stands in for absent experts.
    """

    def __init__(self, num_heads, qk_nope_dim, qk_rope_dim, v_dim, kv_rank,
                 ffn_width=0, n_experts=0, top_k=0, n_shared=0,
                 expert_width=0, routed_scale=1.0, rope_theta=10000.0,
                 epsilon=1e-6, experts_held=None, out_scale=1.0):
        self.num_heads = int(num_heads)
        self.qk_nope_dim = int(qk_nope_dim)
        self.qk_rope_dim = int(qk_rope_dim)
        self.v_dim = int(v_dim)
        self.kv_rank = int(kv_rank)
        self.ffn_width = int(ffn_width)
        self.n_experts = int(n_experts)
        self.top_k = int(top_k)
        self.n_shared = int(n_shared)
        self.expert_width = int(expert_width)
        self.routed_scale = float(routed_scale)
        self.rope_theta = float(rope_theta)
        self.epsilon = float(epsilon)
        self.experts_held = (
            None if experts_held is None else [int(e) for e in experts_held]
        )
        self.out_scale = float(out_scale)
        if self.n_experts:
            self._check_experts(self.n_experts)
        elif self.ffn_width < 1:
            raise ValueError("a dense block needs ffn_width >= 1")

    def init(self, rng, in_shape):
        d = in_shape[-1]
        ks = iter(jax.random.split(rng, 16))
        params = self._attention_init(ks, d)
        if self.n_experts:
            params["ffn"] = {
                "router": self._router_init(ks, d, self.n_experts),
                "experts": self._mlp_init(ks, d, self.expert_width,
                                          (len(self.held),)),
                "shared": self._mlp_init(
                    ks, d, self.n_shared * self.expert_width),
            }
        else:
            params["ffn"] = self._mlp_init(ks, d, self.ffn_width)
        return params, {}, in_shape

    # -- the arithmetic, once -----------------------------------------------

    def ffn(self, p, x, token_mask=None):
        """``x`` ``(n, d)`` -> ``(y, Picks | None)``."""
        if not self.n_experts:
            return gated_mlp(p, x), None
        chosen, w = route(p["router"], x, self.top_k, self.routed_scale)
        y, picks = routed_experts(
            p["experts"], x, chosen, w, self.held, self.n_experts, token_mask
        )
        with jax.named_scope("moe/shared"):
            y = y + gated_mlp(p["shared"], x)
        return y, picks

    def forward(self, p, x, pos, mask, exchange=None, absorbed=False,
                token_mask=None, n_keys=None):
        """``x`` ``(B, n, d)`` float32 at positions ``pos`` ``(B, n)``;
        ``exchange(new (B, n, latent_width) f32) -> (B, t, latent_width)``
        stores the tokens' latent rows and returns what they attend over
        (None: their own; for the absorbed form it may return a callable
        that attends in place, see ``attend_absorbed``); ``mask`` ``(B|1,
        n, t)`` (None with such a callable); ``n_keys``: how many
        of the ``t`` positions can be attended at all (a traced scalar, the
        chunk's form; see ``attend_expanded``). Returns ``(y, Picks |
        None)``."""
        x = x.astype(jnp.float32)
        b, n, d = x.shape
        x = latent_attention(self, p, x, pos, mask, exchange, absorbed,
                             n_keys)
        h = rms_norm(x, p["ln2"]["gamma"], self.epsilon)
        if token_mask is not None:
            token_mask = jnp.broadcast_to(token_mask, (b, n)).reshape(-1)
        y, picks = self.ffn(p["ffn"], h.reshape(b * n, d), token_mask)
        return x + y.reshape(b, n, d), picks

    def get_config(self):
        return {
            "layer": "LatentMoEBlock", "num_heads": self.num_heads,
            "qk_nope_dim": self.qk_nope_dim, "qk_rope_dim": self.qk_rope_dim,
            "v_dim": self.v_dim, "kv_rank": self.kv_rank,
            "ffn_width": self.ffn_width, "n_experts": self.n_experts,
            "top_k": self.top_k, "n_shared": self.n_shared,
            "expert_width": self.expert_width,
            "routed_scale": self.routed_scale, "rope_theta": self.rope_theta,
            "epsilon": self.epsilon, "experts_held": self.experts_held,
            "out_scale": self.out_scale,
        }


@register_layer
class ShortcutMoEBlock(_LatentBlock):
    """One layer of the shortcut-connected form: two latent attentions
    and two gated MLPs of ``ffn_width`` in a row, and an expert layer that
    reads the hidden state after the first attention and whose result is
    added at the layer's end (so it can run beside everything between)::

        h1 = x  + MLA_0(RMSNorm(x));   u = RMSNorm(h1);   m = MoE(u)
        h2 = h1 + MLP_0(u)
        h3 = h2 + MLA_1(RMSNorm(h2))
        h4 = h3 + MLP_1(RMSNorm(h3));  y = h4 + m

    Each attention has a low-rank query (``q_rank``) and, with
    ``scale_q`` / ``scale_kv``, the two factors of ``latent_attention``.
    The router has ``n_experts + n_zero`` outputs: softmax scores, the top
    ``top_k`` of score + selection bias, weights ``routed_scale`` x score,
    not normalised. A pick below ``n_experts`` is a routed expert of width
    ``expert_width`` (``experts_held``: as ``LatentMoEBlock``); a pick at
    or above it is a zero-compute expert, the identity: it adds ``weight x
    u``, every one of them computed here, as one weighted sum that costs no
    product. The block caches two latent rows a token, one an attention,
    and hands ``exchange`` each attention's new rows in turn."""

    cached_rows = 2
    token_block = 1024  # tokens whose picks the expert layer sorts at once

    def __init__(self, num_heads, qk_nope_dim, qk_rope_dim, v_dim, kv_rank,
                 q_rank, ffn_width, n_experts, n_zero, top_k, expert_width,
                 routed_scale=1.0, rope_theta=10000.0, epsilon=1e-5,
                 scale_q=True, scale_kv=True, experts_held=None,
                 out_scale=1.0):
        self.num_heads = int(num_heads)
        self.qk_nope_dim = int(qk_nope_dim)
        self.qk_rope_dim = int(qk_rope_dim)
        self.v_dim = int(v_dim)
        self.kv_rank = int(kv_rank)
        self.q_rank = int(q_rank)
        self.ffn_width = int(ffn_width)
        self.n_experts = int(n_experts)
        self.n_zero = int(n_zero)
        self.top_k = int(top_k)
        self.expert_width = int(expert_width)
        self.routed_scale = float(routed_scale)
        self.rope_theta = float(rope_theta)
        self.epsilon = float(epsilon)
        self.scale_q, self.scale_kv = bool(scale_q), bool(scale_kv)
        self.experts_held = (
            None if experts_held is None else [int(e) for e in experts_held]
        )
        self.out_scale = float(out_scale)
        self._check_experts(self.n_experts + self.n_zero)

    def init(self, rng, in_shape):
        d = in_shape[-1]
        ks = iter(jax.random.split(rng, 32))

        def half():
            return {**self._attention_init(ks, d, self.q_rank),
                    "mlp": self._mlp_init(ks, d, self.ffn_width)}

        params = {
            "0": half(), "1": half(),
            "moe": {
                "router": self._router_init(
                    ks, d, self.n_experts + self.n_zero),
                "experts": self._mlp_init(ks, d, self.expert_width,
                                          (len(self.held),)),
            },
        }
        return params, {}, in_shape

    def moe(self, p, u, token_mask=None):
        """``u`` ``(n, d)`` -> ``(m, Picks)``: the held routed experts'
        part and every identity pick's. More than ``token_block`` tokens
        (a long prefill chunk) go ``token_block`` at a time: a layer that
        holds every expert moves a row of ``d`` for every (token, pick)
        pair, ``top_k`` a token (a chip's share moves its own pairs'
        rows alone, ``routed_experts``)."""
        n, tb = u.shape[0], self.token_block
        if n <= tb or n % tb:
            return self._moe(p, u, token_mask)
        if token_mask is None:
            token_mask = jnp.ones((n,), bool)
        y, picks = jax.lax.map(
            lambda block: self._moe(p, *block),
            (u.reshape(-1, tb, u.shape[-1]), token_mask.reshape(-1, tb)),
        )
        return y.reshape(u.shape), jax.tree.map(lambda a: a.sum(0), picks)

    def _moe(self, p, u, token_mask):
        chosen, w = route(p["router"], u, self.top_k, self.routed_scale,
                          softmax=True)
        y, picks = routed_experts(
            p["experts"], u, chosen, w, self.held,
            self.n_experts + self.n_zero, token_mask,
        )
        with jax.named_scope("moe/zero"):
            zero = chosen >= self.n_experts
            y = y + jnp.sum(jnp.where(zero, w, 0.0), axis=-1,
                            keepdims=True) * u
            if token_mask is not None:
                zero = zero & token_mask[:, None]
            return y, picks._replace(zero=jnp.sum(zero))

    def forward(self, p, x, pos, mask, exchange=None, absorbed=False,
                token_mask=None, n_keys=None):
        """As ``LatentMoEBlock.forward``; ``exchange`` is called twice,
        with the first attention's new rows and then the second's."""
        x = x.astype(jnp.float32)
        b, n, d = x.shape
        h = latent_attention(self, p["0"], x, pos, mask, exchange, absorbed,
                             n_keys)
        u = rms_norm(h, p["0"]["ln2"]["gamma"], self.epsilon)
        if token_mask is not None:
            token_mask = jnp.broadcast_to(token_mask, (b, n)).reshape(-1)
        m, picks = self.moe(p["moe"], u.reshape(b * n, d), token_mask)
        with jax.named_scope("ffn/dense"):
            h = h + gated_mlp(p["0"]["mlp"], u)
        h = latent_attention(self, p["1"], h, pos, mask, exchange, absorbed,
                             n_keys)
        with jax.named_scope("ffn/dense"):
            h = h + gated_mlp(
                p["1"]["mlp"],
                rms_norm(h, p["1"]["ln2"]["gamma"], self.epsilon))
        return h + m.reshape(b, n, d), picks

    def get_config(self):
        return {
            "layer": "ShortcutMoEBlock", "num_heads": self.num_heads,
            "qk_nope_dim": self.qk_nope_dim, "qk_rope_dim": self.qk_rope_dim,
            "v_dim": self.v_dim, "kv_rank": self.kv_rank,
            "q_rank": self.q_rank, "ffn_width": self.ffn_width,
            "n_experts": self.n_experts, "n_zero": self.n_zero,
            "top_k": self.top_k, "expert_width": self.expert_width,
            "routed_scale": self.routed_scale, "rope_theta": self.rope_theta,
            "epsilon": self.epsilon, "scale_q": self.scale_q,
            "scale_kv": self.scale_kv, "experts_held": self.experts_held,
            "out_scale": self.out_scale,
        }


def routing_counts(picks_by_layer):
    """The step's routing counters from the expert layers' ``Picks``:
    ``[sum over layers of the held experts that got a token, the largest
    token count on one expert, the picks of an identity expert, the picks
    of a held routed expert]`` as int32, and behind them, where a layer
    compacts its held rows, how many layers needed more than one pass."""
    sizes = [p.sizes for p in picks_by_layer]
    hit = sum(jnp.sum(s > 0) for s in sizes)
    load = jnp.max(jnp.stack([jnp.max(s) for s in sizes]))
    zero = sum(p.zero for p in picks_by_layer)
    held = sum(jnp.sum(s) for s in sizes)
    over = [p.overflow for p in picks_by_layer if p.overflow is not None]
    return jnp.stack(
        [hit, load, zero, held] + ([sum(over)] if over else [])
    ).astype(jnp.int32)
