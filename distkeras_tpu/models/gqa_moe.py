"""The block of grouped-query attention with window and full layers, a
gate a head, and a gated MLP or routed experts with a shared expert
(the ``laguna`` model type), written once.

``GroupedQueryMoEBlock.forward(p, x, pos, mask, attend)`` is the layer's
arithmetic for all of its uses, with the cache behind ``attend`` as
``TransformerBlock.forward`` and ``LatentMoEBlock.forward`` have it:

- ``apply`` (``Sequential.apply``, ``eval_shape``, the CPU tests): the
  full causal forward, every key of the sequence at once under ``mask``;
- the serving engine's prefill-chunk program: a chunk of one sequence's
  tokens; ``attend`` writes the chunk's keys and values to the slot's
  pages and attends what the layer may see (``attend_blocked``);
- the serving engine's decode-step program: one token a slot; ``attend``
  writes the token's key and value and reads the slot's own pages where
  they lie (``ops.paged_attention.paged_decode_attention``).

A layer is::

    h = RMSNorm(x);  q = h Wq (H heads);  k = h Wk, v = h Wv (Hkv heads)
    g = sigmoid(h Wg) (H,)                      (``gate="per_head"``)
    q, k rotated by position (``rope``: plain, or YaRN on a part of a head)
    a_j = softmax_s(q_j . k_{j // (H / Hkv)}[s] / sqrt(Dh)) v[s],
          s <= t and, with a window, s > t - window
    x = x + concat_j(g_j a_j) Wo
    u = RMSNorm(x);  x = x + FFN(u)

``FFN`` is a gated SiLU MLP (``ffn_width``), or ``n_experts`` routed
experts (softmax scores, the ``top_k`` largest, normalised over the picks
with ``norm_topk``, times ``routed_scale``) plus one shared expert of
``shared_width`` on every token. What the head count, the window and the
rotary settings are is a property of the LAYER: a model mixes blocks that
differ in them, and the serving engine reads ``kind``, ``kv_heads``,
``head_dim`` and ``window`` of each block, never its class.

The arithmetic that the blocks of latent attentions already have
(``rms_norm``, ``rope``, ``gated_mlp``, ``route``, ``routed_experts``, the
``moe/*`` scopes) is imported from ``models/mla_moe.py``; products take
their operands in the weights' dtype and accumulate in float32 as there.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from distkeras_tpu.models.layers import Layer, register_layer
from distkeras_tpu.models.mla_moe import (
    _einsum, _normal, gated_mlp, matmul, rms_norm, rope, route,
    routed_experts)


def yarn_frequencies(n, theta, factor, original, beta_fast, beta_slow):
    """The ``n / 2`` pair frequencies of YaRN-scaled rotary positions:
    ``f_i = theta^(-2i/n)`` where a pair turns more than ``beta_fast``
    times over the ``original`` positions, ``f_i / factor`` where it
    turns fewer than ``beta_slow`` times, and between the two correction
    dimensions (floor and ceiling of where the turns equal the betas) a
    linear ramp from the one to the other."""
    f = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)

    def dim_of(turns):
        return n * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), n - 1)
    ramp = np.clip((np.arange(n // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f / factor) * ramp + f * (1 - ramp)


def attend_dense(q, k, v, mask):
    """Grouped-query attention with every key at once: ``q`` ``(B, n, H,
    Dh)``, ``k``/``v`` ``(B, t, Hkv, Dh)``, ``mask`` ``(B|1, n, t)``;
    query head ``j`` reads K/V head ``j // (H / Hkv)``."""
    b, n, nh, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, n, kvh, nh // kvh, hd)
    s = _einsum("bnkgd,btkd->bkgnt", qg, k) / np.sqrt(hd)
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    o = _einsum("bkgnt,btkd->bnkgd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, n, nh, hd)


def attend_blocked(q, k, v, qpos, kpos0, window=None, key_block=512,
                   query_block=1024):
    """The same attention for a prefill chunk, done for the keys a query
    can see and no others: ``q`` ``(n, H, Dh)`` at positions ``qpos``
    ``(n,)`` (ascending), ``k``/``v`` ``(t, Hkv, Dh)`` at positions
    ``kpos0 + arange(t)`` (a key at a negative position does not exist).
    Queries go ``query_block`` at a time; each folds the key blocks from
    its first visible key (``qpos - window + 1`` with a window, else the
    first key) to its own last position into a running softmax."""
    n, nh, hd = q.shape
    t, kvh = k.shape[0], k.shape[1]
    g = nh // kvh
    scale = 1.0 / np.sqrt(hd)
    if t % key_block:
        pad = -t % key_block  # keys past every query: never visible
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
        t += pad

    def rows(args):
        qb, at = args  # (m, H, Dh), (m,)
        m = qb.shape[0]
        qg = qb.reshape(m, kvh, g, hd)
        first = 0 if window is None else at[0] - window + 1
        lo = jnp.clip((first - kpos0) // key_block, 0, t // key_block)
        hi = jnp.clip((at[-1] - kpos0) // key_block + 1, lo,
                      t // key_block)

        def fold(j, carry):
            mx, l, acc = carry
            off = j * key_block
            kb = jax.lax.dynamic_slice_in_dim(k, off, key_block, 0)
            vb = jax.lax.dynamic_slice_in_dim(v, off, key_block, 0)
            kp = kpos0 + off + jnp.arange(key_block)
            s = _einsum("mkgd,tkd->kgmt", qg, kb) * scale
            see = (kp[None, :] <= at[:, None]) & (kp[None, :] >= 0)
            if window is not None:
                see = see & (kp[None, :] > at[:, None] - window)
            s = jnp.where(see[None, None], s, -jnp.inf)
            m_new = jnp.maximum(mx, s.max(axis=-1))
            # a row with no key yet keeps -inf: exp(-inf - 0) = 0
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            w = jnp.exp(s - safe[..., None])
            fix = jnp.exp(mx - safe)
            l = l * fix + w.sum(axis=-1)
            acc = acc * fix[..., None] + _einsum("kgmt,tkd->kgmd", w, vb)
            return m_new, l, acc

        _, l, acc = jax.lax.fori_loop(lo, hi, fold, (
            jnp.full((kvh, g, m), -jnp.inf, jnp.float32),
            jnp.zeros((kvh, g, m), jnp.float32),
            jnp.zeros((kvh, g, m, hd), jnp.float32),
        ))
        o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return o.transpose(2, 0, 1, 3).reshape(m, nh, hd)

    if n > query_block and n % query_block == 0:
        nb = n // query_block
        o = jax.lax.map(rows, (q.reshape(nb, query_block, nh, hd),
                               qpos.reshape(nb, query_block)))
        return o.reshape(n, nh, hd)
    return rows((q, qpos))


@register_layer
class GroupedQueryMoEBlock(Layer):
    """One pre-RMSNorm layer of grouped-query attention (``num_heads``
    query heads over ``kv_heads`` K/V heads of ``head_dim``; ``window``:
    the last that many positions, None = all) with a sigmoid gate a head
    (``gate="per_head"``; None: no gate) and rotary positions (``rope``:
    ``{"theta", "partial" (the share of a head that turns), and for YaRN
    "factor", "original", "beta_fast", "beta_slow", "attention_factor"}``),
    then a gated MLP (``n_experts=0``: width ``ffn_width``) or an expert
    layer (``n_experts`` routed experts of ``expert_width``, ``top_k`` a
    token, plus a shared expert of ``shared_width``).

    ``experts_held``: as ``LatentMoEBlock``: the routed experts this layer
    holds (ids; None = all); the router keeps its width and its ``top_k``,
    the layer computes the held experts' part, nothing stands in for the
    others. The block caches ``kv_heads`` keys and values of ``head_dim``
    a token, and of a window layer only the last ``window`` are ever read.
    """

    kind = "gqa"
    causal = True
    token_block = 1024  # tokens whose FFN runs at once (a long chunk)
    key_block = 512  # cache positions a prefill chunk folds at once
    _std = 0.02

    def __init__(self, num_heads, kv_heads, head_dim, rope, window=None,
                 gate="per_head", ffn_width=0, n_experts=0, top_k=0,
                 expert_width=0, shared_width=0, routed_scale=1.0,
                 norm_topk=True, epsilon=1e-6, experts_held=None,
                 out_scale=1.0):
        self.num_heads = int(num_heads)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.rope = dict(rope)
        self.window = None if window is None else int(window)
        self.gate = gate
        self.ffn_width = int(ffn_width)
        self.n_experts = int(n_experts)
        self.top_k = int(top_k)
        self.expert_width = int(expert_width)
        self.shared_width = int(shared_width)
        self.routed_scale = float(routed_scale)
        self.norm_topk = bool(norm_topk)
        self.epsilon = float(epsilon)
        self.experts_held = (
            None if experts_held is None else [int(e) for e in experts_held]
        )
        self.out_scale = float(out_scale)
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads are not a multiple of "
                f"{self.kv_heads} K/V heads")
        if gate not in (None, "per_head"):
            raise ValueError(f"gate {gate!r}: 'per_head' or None")
        rot = self.rotary_dim
        if rot < 2 or rot % 2 or rot > self.head_dim:
            raise ValueError(f"rotary part {rot} of heads of {head_dim}")
        if self.n_experts:
            held = self.held
            if not 1 <= self.top_k <= self.n_experts or (
                    len(set(held)) != len(held)
                    or not all(0 <= e < self.n_experts for e in held)):
                raise ValueError(
                    f"expert layer: top_k {self.top_k} of "
                    f"{self.n_experts} routed experts, held {held}")
        elif self.ffn_width < 1:
            raise ValueError("a dense block needs ffn_width >= 1")

    @property
    def held(self) -> list:
        if self.experts_held is None:
            return list(range(self.n_experts))
        return self.experts_held

    @property
    def rotary_dim(self) -> int:
        return int(round(self.head_dim * float(self.rope.get("partial", 1))))

    def _rope_args(self):
        """``(freq | None, factor)`` for ``mla_moe.rope``."""
        r = self.rope
        if "factor" not in r:
            return None, 1.0
        freq = yarn_frequencies(
            self.rotary_dim, float(r["theta"]), float(r["factor"]),
            float(r["original"]), float(r["beta_fast"]),
            float(r["beta_slow"]))
        return (jnp.asarray(freq, jnp.float32),
                float(r.get("attention_factor", 1.0)))

    def rotate(self, x, pos):
        """``x`` ``(..., heads, head_dim)`` at ``pos`` ``(...)``: the
        leading ``rotary_dim`` values of each head turned, the rest as
        they are."""
        rot = self.rotary_dim
        freq, factor = self._rope_args()
        turned = rope(x[..., :rot], pos[..., None], float(self.rope["theta"]),
                      freq, factor)
        if rot == self.head_dim:
            return turned
        return jnp.concatenate(
            [turned, x[..., rot:].astype(jnp.float32)], axis=-1)

    def init(self, rng, in_shape):
        d = in_shape[-1]
        ks = iter(jax.random.split(rng, 16))
        std, dt = self._std, jnp.float32
        nh, kvh, hd = self.num_heads, self.kv_heads, self.head_dim

        def mlp(width, lead=()):
            return {"wg": _normal(next(ks), (*lead, d, width), std, dt),
                    "wu": _normal(next(ks), (*lead, d, width), std, dt),
                    "wd": _normal(next(ks), (*lead, width, d),
                                  std * self.out_scale, dt)}

        attn = {"wq": _normal(next(ks), (d, nh * hd), std, dt),
                "wk": _normal(next(ks), (d, kvh * hd), std, dt),
                "wv": _normal(next(ks), (d, kvh * hd), std, dt),
                "wo": _normal(next(ks), (nh * hd, d),
                              std * self.out_scale, dt)}
        if self.gate:
            attn["wgate"] = _normal(next(ks), (d, nh), std, dt)
        params = {"ln1": {"gamma": jnp.ones((d,), dt)}, "attn": attn,
                  "ln2": {"gamma": jnp.ones((d,), dt)}}
        if self.n_experts:
            params["ffn"] = {
                "router": {"wr": _normal(next(ks), (d, self.n_experts),
                                         std, dt)},
                "experts": mlp(self.expert_width, (len(self.held),)),
                "shared": mlp(self.shared_width),
            }
        else:
            params["ffn"] = mlp(self.ffn_width)
        return params, {}, in_shape

    # -- the arithmetic, once -----------------------------------------------

    def attention(self, p, x, pos, mask, attend=None):
        """``x + Wo(gate * Attn(RMSNorm(x)))``; ``x`` ``(..., d)`` at
        ``pos`` ``(...)``. ``attend(q (..., H, Dh), k_new, v_new (...,
        Hkv, Dh)) -> (..., H, Dh)`` owns the cache: where the new keys
        and values go and what is attended. None (``apply``): the
        sequence's own keys under ``mask`` ``(B|1, n, t)``."""
        a = p["attn"]
        lead = x.shape[:-1]
        nh, kvh, hd = self.num_heads, self.kv_heads, self.head_dim
        scope = "attn/window" if self.window is not None else "attn/full"
        with jax.named_scope(scope):
            h = rms_norm(x, p["ln1"]["gamma"], self.epsilon)
            q = self.rotate(matmul(h, a["wq"]).reshape(*lead, nh, hd), pos)
            k = self.rotate(matmul(h, a["wk"]).reshape(*lead, kvh, hd), pos)
            v = matmul(h, a["wv"]).reshape(*lead, kvh, hd)
            if attend is None:
                cd = a["wk"].dtype  # as a served cache holds them
                o = attend_dense(q, k.astype(cd), v.astype(cd), mask)
            else:
                o = attend(q, k, v)
            if self.gate:
                o = o * jax.nn.sigmoid(matmul(h, a["wgate"]))[..., None]
            return x + matmul(o.reshape(*lead, nh * hd), a["wo"])

    def ffn(self, p, u, token_mask=None):
        """``u`` ``(n, d)`` -> ``(y, Picks | None)``; more than
        ``token_block`` tokens (a long prefill chunk) go ``token_block``
        at a time, since the expert layer sorts ``top_k`` rows of ``d`` a
        token and the dense MLP is 4 x ``d`` wide."""
        n, tb = u.shape[0], self.token_block
        if n <= tb or n % tb:
            return self._ffn(p, u, token_mask)
        if token_mask is None:
            token_mask = jnp.ones((n,), bool)
        y, picks = jax.lax.map(
            lambda block: self._ffn(p, *block),
            (u.reshape(-1, tb, u.shape[-1]), token_mask.reshape(-1, tb)),
        )
        return y.reshape(u.shape), jax.tree.map(lambda a: a.sum(0), picks)

    def _ffn(self, p, u, token_mask):
        if not self.n_experts:
            with jax.named_scope("ffn/dense"):
                return gated_mlp(p, u), None
        chosen, w = route(p["router"], u, self.top_k, self.routed_scale,
                          softmax=True, normalise=self.norm_topk)
        y, picks = routed_experts(
            p["experts"], u, chosen, w, self.held, self.n_experts,
            token_mask)
        with jax.named_scope("moe/shared"):
            y = y + gated_mlp(p["shared"], u)
        return y, picks

    def forward(self, p, x, pos, mask, attend=None, token_mask=None):
        """``x`` ``(..., d)`` at positions ``pos`` ``(...)``; ``attend``
        and ``mask`` as :meth:`attention`; ``token_mask`` ``(...)``: the
        tokens whose expert picks count (a slot that is not decoding
        routes nothing). Returns ``(y float32, Picks | None)``."""
        x = x.astype(jnp.float32)
        lead, d = x.shape[:-1], x.shape[-1]
        x = self.attention(p, x, pos, mask, attend)
        u = rms_norm(x, p["ln2"]["gamma"], self.epsilon)
        if token_mask is not None:
            token_mask = jnp.broadcast_to(token_mask, lead).reshape(-1)
        y, picks = self.ffn(p["ffn"], u.reshape(-1, d), token_mask)
        return x + y.reshape(*lead, d), picks

    def apply(self, params, state, x, train=False, rng=None):
        b, n, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(n), (b, n))
        at = jnp.arange(n)
        mask = at[None, :] <= at[:, None]
        if self.window is not None:
            mask = mask & (at[None, :] > at[:, None] - self.window)
        y, _ = self.forward(params, x, pos, mask[None])
        return y, state

    def get_config(self):
        return {
            "layer": "GroupedQueryMoEBlock", "num_heads": self.num_heads,
            "kv_heads": self.kv_heads, "head_dim": self.head_dim,
            "rope": self.rope, "window": self.window, "gate": self.gate,
            "ffn_width": self.ffn_width, "n_experts": self.n_experts,
            "top_k": self.top_k, "expert_width": self.expert_width,
            "shared_width": self.shared_width,
            "routed_scale": self.routed_scale, "norm_topk": self.norm_topk,
            "epsilon": self.epsilon, "experts_held": self.experts_held,
            "out_scale": self.out_scale,
        }
