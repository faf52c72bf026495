"""The latent-attention / routed-expert block (the ``deepseek_v3`` model
type), written once.

One function, ``LatentMoEBlock.forward``, is the layer's arithmetic for
all three of its uses:

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

Block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; FFN is
a gated SiLU MLP, or an expert layer (sigmoid scores over all routed
experts, top-k of score + selection bias, chosen scores normalised and
scaled; plus shared experts on every token). The cache holds, a token and
a layer, the normalised latent ``cn`` (``kv_rank``) and the rotated
shared key ``k_pe`` (``rope_dim``), and nothing else.

Matrix products take their operands in the weights' dtype (float32 as
initialised, bfloat16 as served) and accumulate in float32; norms,
softmax, the router and the residual stream are float32.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from distkeras_tpu.models.layers import Layer, register_layer


class BlockUnsupportedError(NotImplementedError):
    """A serving feature that the latent-attention block cannot run yet
    (the dense slot bank, speculation, fork/beam, ``tp`` meshes, K/V
    export, swap-out). Not a ``ValueError``: ``ServingEngine`` demotes a
    model whose stepper raises one to predict-only, and a refused feature
    must fail the boot instead."""


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
    """Rows of ``x`` sorted by group against the stacked ``(G, k, m)``
    weights, each group over its own rows (``jax.lax.ragged_dot``), in the
    weights' dtype, float32 out."""
    return jax.lax.ragged_dot(*_operands(x, w), sizes,
                              preferred_element_type=jnp.float32)


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * gamma.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotate the pairs ``(2i, 2i+1)`` of the last axis by ``pos *
    theta^(-2i/n)`` (the interleaved pairing; ``n`` the axis' size).
    ``pos`` broadcasts against ``x``'s leading axes."""
    n = x.shape[-1]
    freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1
    ).reshape(x.shape)


def gated_mlp(p, x):
    """``(silu(x Wg) * (x Wu)) Wd``."""
    return matmul(jax.nn.silu(matmul(x, p["wg"])) * matmul(x, p["wu"]), p["wd"])


def route(p, x, top_k, scale):
    """Sigmoid scores over ALL routed experts in float32; the top ``k`` of
    score + selection bias; weights = chosen scores over their sum, times
    ``scale``. Returns ``(chosen (n, k) int32, weights (n, k) f32)``."""
    with jax.named_scope("moe/route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["wr"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ))
        _, chosen = jax.lax.top_k(s + p["bias"].astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
        return chosen.astype(jnp.int32), w


def routed_experts(p, x, chosen, weights, held, n_experts, token_mask=None):
    """The held experts' part of the routed output, no token dropped:
    the (token, choice) pairs are sorted by expert and the three products
    run as grouped products (``jax.lax.ragged_dot``) over the stacked
    ``(E_held, in, out)`` weights, each expert over exactly the rows
    routed to it. A pair whose expert is not held here (or whose token
    ``token_mask`` switches off) adds nothing and costs no product.

    Returns ``(y (n, d) f32, group_sizes (E_held,) int32)``."""
    n, k = chosen.shape
    e_held = p["wg"].shape[0]
    local = np.full((n_experts + 1,), e_held, np.int32)  # sentinel: absent
    local[np.asarray(held, np.int64)] = np.arange(e_held, dtype=np.int32)
    with jax.named_scope("moe/experts"):
        flat = jnp.asarray(local)[chosen.reshape(-1)]  # (n k,) local ids
        if token_mask is not None:
            flat = jnp.where(jnp.repeat(token_mask, k), flat, e_held)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=e_held + 1)[:e_held].astype(
            jnp.int32
        )
        xs = x[order // k]
        h = jax.nn.silu(_grouped_mm(xs, p["wg"], sizes)) * _grouped_mm(
            xs, p["wu"], sizes)
        y = _grouped_mm(h, p["wd"], sizes)  # (n k, d), sorted by expert
        # rows past the held groups belong to no expert: weight 0
        wsorted = jnp.where(
            flat[order] < e_held, weights.reshape(-1)[order], 0.0
        )
        y = jnp.where(wsorted[:, None] != 0.0, y * wsorted[:, None], 0.0)
        back = jnp.argsort(order)  # sorted row of each (token, choice)
        return y[back].reshape(n, k, -1).sum(axis=1), sizes


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


@register_layer
class LatentMoEBlock(Layer):
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

    kind = "latent"
    causal = True
    key_block = 512  # cache positions a prefill chunk attends at once

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
            held = self.held
            if not 1 <= self.top_k <= self.n_experts or (
                    len(set(held)) != len(held)
                    or not all(0 <= e < self.n_experts for e in held)):
                raise ValueError(
                    f"expert layer: top_k {self.top_k} of {self.n_experts} "
                    f"experts, held {held}"
                )
        elif self.ffn_width < 1:
            raise ValueError("a dense block needs ffn_width >= 1")

    @property
    def held(self) -> list:
        if self.experts_held is None:
            return list(range(self.n_experts))
        return self.experts_held

    @property
    def latent_width(self) -> int:
        """Values cached a token: the latent and the shared rotary key."""
        return self.kv_rank + self.qk_rope_dim

    # -- parameters ---------------------------------------------------------

    def init(self, rng, in_shape):
        d = in_shape[-1]
        dt = jnp.float32
        nh, dq = self.num_heads, self.qk_nope_dim + self.qk_rope_dim
        ks = iter(jax.random.split(rng, 16))
        std = 0.02  # N(0, 0.02); output projections scaled by out_scale
        out = std * self.out_scale

        def mlp(width, lead=()):
            return {"wg": _normal(next(ks), (*lead, d, width), std, dt),
                    "wu": _normal(next(ks), (*lead, d, width), std, dt),
                    "wd": _normal(next(ks), (*lead, width, d), out, dt)}

        params = {
            "ln1": {"gamma": jnp.ones((d,), dt)},
            "attn": {
                "wq": _normal(next(ks), (d, nh * dq), std, dt),
                "wkva": _normal(next(ks), (d, self.latent_width), std, dt),
                "kv_norm": {"gamma": jnp.ones((self.kv_rank,), dt)},
                "wkvb": _normal(
                    next(ks),
                    (self.kv_rank, nh * (self.qk_nope_dim + self.v_dim)),
                    std, dt),
                "wo": _normal(next(ks), (nh * self.v_dim, d), out, dt),
            },
            "ln2": {"gamma": jnp.ones((d,), dt)},
        }
        if self.n_experts:
            params["ffn"] = {
                "router": {
                    "wr": _normal(next(ks), (d, self.n_experts), std, dt),
                    "bias": jnp.zeros((self.n_experts,), dt),
                },
                "experts": mlp(self.expert_width, (len(self.held),)),
                "shared": mlp(self.n_shared * self.expert_width),
            }
        else:
            params["ffn"] = mlp(self.ffn_width)
        return params, {}, in_shape

    # -- the arithmetic, once -----------------------------------------------

    def ffn(self, p, x, token_mask=None):
        """``x`` ``(n, d)`` -> ``(y, group_sizes | None)``."""
        if not self.n_experts:
            return gated_mlp(p, x), None
        chosen, w = route(p["router"], x, self.top_k, self.routed_scale)
        y, sizes = routed_experts(
            p["experts"], x, chosen, w, self.held, self.n_experts, token_mask
        )
        with jax.named_scope("moe/shared"):
            y = y + gated_mlp(p["shared"], x)
        return y, sizes

    def forward(self, p, x, pos, mask, exchange=None, absorbed=False,
                token_mask=None, n_keys=None):
        """``x`` ``(B, n, d)`` float32 at positions ``pos`` ``(B, n)``;
        ``exchange(new (B, n, latent_width) f32) -> (B, t, latent_width)``
        stores the tokens' latent rows and returns what they attend over
        (None: their own; for the absorbed form it may return a callable
        that attends in place, see ``attend_absorbed``); ``mask`` ``(B|1,
        n, t)`` (None with such a callable); ``n_keys``: how many
        of the ``t`` positions can be attended at all (a traced scalar, the
        chunk's form; see ``attend_expanded``). Returns ``(y, group_sizes |
        None)``."""
        a = p["attn"]
        x = x.astype(jnp.float32)
        b, n, d = x.shape
        nh, nope, vd = self.num_heads, self.qk_nope_dim, self.v_dim
        with jax.named_scope("mla"):
            h = rms_norm(x, p["ln1"]["gamma"], self.epsilon)
            q = matmul(h, a["wq"]).reshape(b, n, nh, nope + self.qk_rope_dim)
            q = jnp.concatenate([
                q[..., :nope],
                rope(q[..., nope:], pos[..., None], self.rope_theta),
            ], axis=-1)
            ckv = matmul(h, a["wkva"])
            new = jnp.concatenate([
                rms_norm(ckv[..., :self.kv_rank], a["kv_norm"]["gamma"],
                         self.epsilon),
                rope(ckv[..., self.kv_rank:], pos, self.rope_theta),
            ], axis=-1)
            latent = new if exchange is None else exchange(new)
            if absorbed:
                o = attend_absorbed(a, q, latent, mask, nh, nope, vd)
            else:
                o = attend_expanded(a, q, latent, mask, nh, nope, vd, n_keys,
                                    self.key_block)
            x = x + matmul(o, a["wo"])
        h = rms_norm(x, p["ln2"]["gamma"], self.epsilon)
        if token_mask is not None:
            token_mask = jnp.broadcast_to(token_mask, (b, n)).reshape(-1)
        y, sizes = self.ffn(p["ffn"], h.reshape(b * n, d), token_mask)
        return x + y.reshape(b, n, d), sizes

    def apply(self, params, state, x, train=False, rng=None):
        b, n, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(n), (b, n))
        mask = jnp.tril(jnp.ones((n, n), bool))[None]
        y, _ = self.forward(params, x, pos, mask)
        return y, state

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


def routing_counts(sizes_by_layer):
    """The step's two routing counters from the expert layers' group
    sizes: ``[sum over layers of the experts that got a token, the
    largest token count on one expert]`` as int32."""
    hit = sum(jnp.sum(s > 0) for s in sizes_by_layer)
    load = jnp.max(jnp.stack([jnp.max(s) for s in sizes_by_layer]))
    return jnp.stack([hit, load]).astype(jnp.int32)
