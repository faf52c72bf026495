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
  they lie (``ops.paged_attention.paged_decode_attention``; under the
  selection's mask where the block selects).

A layer is::

    h = RMSNorm(x);  q = h Wq (H heads);  k = h Wk, v = h Wv (Hkv heads)
    g = sigmoid(h Wg) (H,)                      (``gate="per_head"``)
    q, k rotated by position (``rope``: plain, or YaRN on a part of a head)
    a_j = softmax_s(q_j . k_{j // (H / Hkv)}[s] / sqrt(Dh)) v[s],
          s <= t and, with a window, s > t - window
    x = x + concat_j(g_j a_j) Wo
    u = RMSNorm(x);  x = x + FFN(u)

With ``qk_norm`` every head of ``q`` and ``k`` is RMS-normalised (one gain
over ``Dh``, shared by the heads) before the rotation. With ``select``
(``{"heads": J, "head_dim": Di, "topk": K}``: a learned indexer, after
DeepSeek-V3.2's) a query reads only the ``K`` cached positions its indexer
scores highest::

    qI = rot(h WqI) (J heads of Di);  kI = rot(LayerNorm(h WkI)) (ONE head of
    Di, cached a token and layer beside k and v);  w = h Ww (J,)
    I[t, s] = (J Di)^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s]),  float32
    S_t = the K positions s <= t of largest I[t, s] (all while t + 1 <= K;
          a tie at the threshold goes to the lower position)
    a_j = softmax over s in S_t alone

The indexer's arithmetic (``index_inputs``, ``index_scores``, ``select_mask``,
``select_rows``) is written once here and used by ``apply``, the chunk
program and the step program; the selection is exact. It has two forms, one
a reader: ``select_mask`` (which keys, as a mask: counting passes, no sort)
for whatever attends under a mask: ``apply``, the chunk's ``attend_selected``
and, where a kernel serves the stepper's shapes, the decode step's
``paged_decode_attention(..., chosen=mask)`` over the slot's own pages;
``select_rows`` (the same keys as positions: ``lax.top_k``) for the step's
gather body, which reads the selected rows by token.

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


def attend_dense(q, k, v, mask, scale=None):
    """Grouped-query attention with every key at once: ``q`` ``(B, n, H,
    Dh)``, ``k``/``v`` ``(B, t, Hkv, Dh)``, ``mask`` ``(B|1, n, t)``;
    query head ``j`` reads K/V head ``j // (H / Hkv)``. ``scale``: what
    multiplies the scores (None: ``1 / sqrt(Dh)``)."""
    b, n, nh, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, n, kvh, nh // kvh, hd)
    s = _einsum("bnkgd,btkd->bkgnt", qg, k)
    s = s / np.sqrt(hd) if scale is None else s * scale
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    o = _einsum("bkgnt,btkd->bnkgd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, n, nh, hd)


def attend_blocked(q, k, v, qpos, kpos0, window=None, key_block=512,
                   query_block=1024, scale=None):
    """The same attention for a prefill chunk, done for the keys a query
    can see and no others: ``q`` ``(n, H, Dh)`` at positions ``qpos``
    ``(n,)`` (ascending), ``k``/``v`` ``(t, Hkv, Dh)`` at positions
    ``kpos0 + arange(t)`` (a key at a negative position does not exist).
    Queries go ``query_block`` at a time; each folds the key blocks from
    its first visible key (``qpos - window + 1`` with a window, else the
    first key) to its own last position into a running softmax. ``scale``:
    what multiplies the scores (None: ``1 / sqrt(Dh)``)."""
    n, nh, hd = q.shape
    t, kvh = k.shape[0], k.shape[1]
    g = nh // kvh
    if scale is None:
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


# ---------------------------------------------------- the exact selection


def _ordered_bits(s):
    """float32 -> uint32 whose unsigned order is the floats' own (-0.0 as
    +0.0, so that equal scores have equal bits); every float lies above 0."""
    b = jax.lax.bitcast_convert_type(
        jnp.where(s == 0.0, 0.0, s).astype(jnp.float32), jnp.int32)
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def _kth_largest(u, k: int):
    """The ``k``-th largest of each row of ``u`` ``(m, t)`` uint32, found
    bit by bit from the top: 32 counting passes, no sort. 0 for a row with
    fewer than ``k`` values above 0."""
    def bit(i, best):
        cand = best | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, best)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))


def _running_count(x):
    """The inclusive running count of a bool ``(m, t)`` along its rows, as
    two products with a triangle of ones (within blocks of 128 positions,
    then over the blocks' totals): exact in float32, and a long
    ``cumsum`` costs the TPU's compiler seconds an instance."""
    m, t = x.shape
    lane = math.gcd(t, 128)
    ones = jnp.ones((), jnp.bfloat16)
    upto = jnp.triu(jnp.full((lane, lane), ones))  # [l, k]: l <= k
    within = _einsum("mbl,lk->mbk", x.reshape(m, t // lane, lane), upto)
    before = jnp.triu(jnp.full((t // lane,) * 2, ones), 1)  # b < c
    offset = _einsum("mb,bc->mc", within[..., -1], before)
    return (within + offset[..., None]).reshape(m, t).astype(jnp.int32)


def select_mask(scores, visible, k: int):
    """Which keys each query selects, as a mask: ``scores`` ``(m, t)``
    float32, ``visible`` ``(m, t)`` bool -> ``(m, t)`` bool with exactly
    ``min(visible, k)`` keys a row, the ``k`` of largest score, a tie at
    the threshold to the lower position. The threshold is the exact
    ``k``-th largest score (``_kth_largest``); where some row has more
    keys AT the threshold than it has room for, those are ranked by
    position (``_running_count``: the rare path, under a ``cond``)."""
    u = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))
    thr = _kth_largest(u, k)[:, None]
    above, ties = u > thr, (u == thr) & visible
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    crowded = jnp.any(jnp.sum(ties, axis=-1, dtype=jnp.int32) > room)
    return above | jax.lax.cond(
        crowded,
        lambda: ties & (_running_count(ties) <= room[:, None]),
        lambda: ties,
    )


def select_rows(scores, visible, k: int):
    """The same selection as positions: ``(idx (m, k'), valid (m, k'))``,
    ``k' = min(k, t)``; ``valid`` is False where a row has fewer visible
    keys than ``k'``. ``lax.top_k`` puts the lower index first among equal
    values, which is the tie rule (-0.0 goes in as +0.0: a sort may tell
    them apart, a score does not)."""
    masked = jnp.where(visible, jnp.where(scores == 0.0, 0.0, scores),
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(k, scores.shape[-1]))
    return idx, jnp.take_along_axis(visible, idx, axis=-1)


def attend_selected(q, keys_of, qpos, chosen_of, extent, extents=(),
                    key_block=512, query_block=256, scale=None):
    """Grouped-query attention for a prefill chunk whose queries each read
    the keys their indexer picks: ``q`` ``(n, H, Dh)`` at positions
    ``qpos`` ``(n,)`` (ascending) against the cached positions ``0 ..
    extent - 1``; ``keys_of(t') -> (k, v)`` ``(t', Hkv, Dh)`` gives the
    first ``t'`` of them, ``chosen_of(lo, m, t') -> (m, t')`` bool which of
    them queries ``lo .. lo + m`` read (the indexer's scores and
    ``select_mask``, under the caller's scope). Queries go ``query_block``
    at a time: their selection, then the key blocks up to their last
    position folded into a running softmax under the selection's mask (a
    product over every visible key: more work than the selected keys
    alone, the same result). ``extents`` (ascending, below ``extent``):
    everything is done at the first extent that holds the chunk's last
    position, under a ``switch``, so that a short context does not pay for
    the longest. ``scale``: what multiplies the scores (None: ``1 /
    sqrt(Dh)``)."""
    n, nh, hd = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    extents = [e for e in extents if e < extent] + [extent]

    def at_extent(te):
        def run():
            k, v = keys_of(te)
            kvh = k.shape[1]
            g = nh // kvh
            kb = min(key_block, te)
            blocks = -(-te // kb)
            pad = blocks * kb - te
            ke, ve = (jnp.pad(c, ((0, pad), (0, 0), (0, 0))) for c in (k, v))

            def rows(lo, m):
                qg = jax.lax.dynamic_slice_in_dim(q, lo, m, 0).reshape(
                    m, kvh, g, hd)
                last = jax.lax.dynamic_index_in_dim(
                    qpos, lo + m - 1, keepdims=False)
                sel = jnp.pad(chosen_of(lo, m, te), ((0, 0), (0, pad)))
                hi = jnp.clip(last // kb + 1, 0, blocks)

                def fold(j, carry):
                    mx, l, acc = carry
                    off = j * kb
                    kj = jax.lax.dynamic_slice_in_dim(ke, off, kb, 0)
                    vj = jax.lax.dynamic_slice_in_dim(ve, off, kb, 0)
                    see = jax.lax.dynamic_slice_in_dim(sel, off, kb, 1)
                    s = _einsum("mkgd,tkd->kgmt", qg, kj) * scale
                    s = jnp.where(see[None, None], s, -jnp.inf)
                    m_new = jnp.maximum(mx, s.max(axis=-1))
                    # a row with no key yet keeps -inf: exp(-inf - 0) = 0
                    safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                    w = jnp.exp(s - safe[..., None])
                    fix = jnp.exp(mx - safe)
                    l = l * fix + w.sum(axis=-1)
                    acc = acc * fix[..., None] + _einsum(
                        "kgmt,tkd->kgmd", w, vj)
                    return m_new, l, acc

                _, l, acc = jax.lax.fori_loop(0, hi, fold, (
                    jnp.full((kvh, g, m), -jnp.inf, jnp.float32),
                    jnp.zeros((kvh, g, m), jnp.float32),
                    jnp.zeros((kvh, g, m, hd), jnp.float32),
                ))
                o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
                return o.transpose(2, 0, 1, 3).reshape(m, nh, hd)

            if n > query_block and n % query_block == 0:
                o = jax.lax.map(lambda lo: rows(lo, query_block),
                                jnp.arange(0, n, query_block))
                return o.reshape(n, nh, hd)
            return rows(0, n)

        return run

    if len(extents) == 1:
        return at_extent(extent)()
    # the first extent that holds the chunk's last position
    which = jnp.sum(qpos[-1] >= jnp.asarray(extents[:-1]))
    return jax.lax.switch(which, [at_extent(e) for e in extents])


@register_layer
class GroupedQueryMoEBlock(Layer):
    """One pre-RMSNorm layer of grouped-query attention (``num_heads``
    query heads over ``kv_heads`` K/V heads of ``head_dim``; ``window``:
    the last that many positions, None = all) with a sigmoid gate a head
    (``gate="per_head"``; None: no gate) and rotary positions (``rope``:
    ``{"theta", "partial" (the share of a head that turns), and for YaRN
    "factor", "original", "beta_fast", "beta_slow", "attention_factor"}``;
    None: no rotation at all, and no position enters the layer),
    then a gated MLP (``n_experts=0``: width ``ffn_width``) or an expert
    layer (``n_experts`` routed experts of ``expert_width``, ``top_k`` a
    token, plus a shared expert of ``shared_width``; 0: none).
    ``qk_norm``: an RMSNorm a head on ``q`` and ``k`` before the rotation.
    ``select`` (``{"heads", "head_dim", "topk"}``; None: every key): the
    indexer whose scores pick the ``topk`` cached positions a query reads;
    such a block caches one selector key of ``select["head_dim"]`` a token
    beside its keys and values, and has no window. ``softmax_scale``:
    what multiplies the scores (None: ``1 / sqrt(head_dim)``);
    ``residual_scale``: what multiplies each branch (the attention's
    output, the FFN's) as it enters the residual stream.

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
    # a selecting block: the most tokens one prefill-chunk program takes
    # (its scores are tokens x cached positions), and the cached extents
    # its chunk scores and selects at, as multiples of ``topk``
    chunk_tokens = 2048
    select_extents = (4, 8, 16)
    _std = 0.02

    def __init__(self, num_heads, kv_heads, head_dim, rope, window=None,
                 gate="per_head", ffn_width=0, n_experts=0, top_k=0,
                 expert_width=0, shared_width=0, routed_scale=1.0,
                 norm_topk=True, epsilon=1e-6, experts_held=None,
                 out_scale=1.0, qk_norm=False, select=None,
                 softmax_scale=None, residual_scale=1.0):
        self.num_heads = int(num_heads)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.rope = None if rope is None else dict(rope)
        self.softmax_scale = (
            None if softmax_scale is None else float(softmax_scale))
        self.residual_scale = float(residual_scale)
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
        self.qk_norm = bool(qk_norm)
        self.select = None if select is None else {
            k: int(select[k]) for k in ("heads", "head_dim", "topk")}
        if self.select is not None and (
                self.window is not None or self.select["head_dim"] % 2
                or min(self.select.values()) < 1):
            raise ValueError(
                f"select {self.select} with window {self.window}: an "
                f"indexer has heads of an even size, topk >= 1 and no "
                f"window beside it")
        if self.select is not None and self.rope is None:
            raise ValueError("an indexer's keys are rotated: it needs rope")
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads are not a multiple of "
                f"{self.kv_heads} K/V heads")
        if gate not in (None, "per_head"):
            raise ValueError(f"gate {gate!r}: 'per_head' or None")
        rot = self.rotary_dim
        if self.rope is not None and (
                rot < 2 or rot % 2 or rot > self.head_dim):
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
        if self.rope is None:
            return 0
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
        they are (without ``rope``: all of them, in float32)."""
        if self.rope is None:
            return x.astype(jnp.float32)
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
        ks = iter(jax.random.split(rng, 19 if self.select else 16))
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
        if self.qk_norm:
            attn["q_norm"] = {"gamma": jnp.ones((hd,), dt)}
            attn["k_norm"] = {"gamma": jnp.ones((hd,), dt)}
        if self.select:
            nj, di = self.select["heads"], self.select["head_dim"]
            attn["index"] = {
                "wq": _normal(next(ks), (d, nj * di), std, dt),
                "wk": _normal(next(ks), (d, di), std, dt),
                "ww": _normal(next(ks), (d, nj), std, dt),
                "norm": {"gamma": jnp.ones((di,), dt),
                         "beta": jnp.zeros((di,), dt)},
            }
        params = {"ln1": {"gamma": jnp.ones((d,), dt)}, "attn": attn,
                  "ln2": {"gamma": jnp.ones((d,), dt)}}
        if self.n_experts:
            params["ffn"] = {
                "router": {"wr": _normal(next(ks), (d, self.n_experts),
                                         std, dt)},
                "experts": mlp(self.expert_width, (len(self.held),)),
            }
            if self.shared_width:
                params["ffn"]["shared"] = mlp(self.shared_width)
        else:
            params["ffn"] = mlp(self.ffn_width)
        return params, {}, in_shape

    # -- the arithmetic, once -----------------------------------------------

    def _qkv(self, a, h, pos):
        """``q`` ``(..., H, Dh)``, ``k``, ``v`` ``(..., Hkv, Dh)`` of the
        normed input ``h``: projected, normalised a head with
        ``qk_norm``, ``q`` and ``k`` rotated by ``pos``."""
        lead = h.shape[:-1]
        nh, kvh, hd = self.num_heads, self.kv_heads, self.head_dim
        q = matmul(h, a["wq"]).reshape(*lead, nh, hd)
        if self.qk_norm:
            q = rms_norm(q, a["q_norm"]["gamma"], self.epsilon)
        q = self.rotate(q, pos)
        k = matmul(h, a["wk"]).reshape(*lead, kvh, hd)
        if self.qk_norm:
            k = rms_norm(k, a["k_norm"]["gamma"], self.epsilon)
        k = self.rotate(k, pos)
        return q, k, matmul(h, a["wv"]).reshape(*lead, kvh, hd)

    def attention(self, p, x, pos, mask, attend=None):
        """``x + Wo(gate * Attn(RMSNorm(x)))``; ``x`` ``(..., d)`` at
        ``pos`` ``(...)``. ``attend(q (..., H, Dh), k_new, v_new (...,
        Hkv, Dh)) -> (..., H, Dh)`` owns the cache: where the new keys
        and values go and what is attended. None (``apply``): the
        sequence's own keys under ``mask`` ``(B|1, n, t)``. A block that
        selects hands ``attend`` a fourth argument, the indexer's
        ``(qI, kI_new, w)`` (:meth:`index_inputs`), and ``attend`` opens
        the ``attn/index`` and ``attn/sparse`` scopes around its parts."""
        if self.select:
            return self._attention_selected(p, x, pos, mask, attend)
        a = p["attn"]
        lead = x.shape[:-1]
        nh, hd = self.num_heads, self.head_dim
        scope = "attn/window" if self.window is not None else "attn/full"
        with jax.named_scope(scope):
            h = rms_norm(x, p["ln1"]["gamma"], self.epsilon)
            q, k, v = self._qkv(a, h, pos)
            if attend is None:
                cd = a["wk"].dtype  # as a served cache holds them
                o = attend_dense(q, k.astype(cd), v.astype(cd), mask,
                                 self.softmax_scale)
            else:
                o = attend(q, k, v)
            if self.gate:
                o = o * jax.nn.sigmoid(matmul(h, a["wgate"]))[..., None]
            return self._into(x, matmul(o.reshape(*lead, nh * hd), a["wo"]))

    def _into(self, x, y):
        """A branch's output into the residual stream."""
        r = self.residual_scale
        return x + (y if r == 1.0 else r * y)

    # -- the indexer ---------------------------------------------------------

    def index_inputs(self, pi, h, pos):
        """What the scores are made of, from the normed input ``h`` ``(...,
        d)`` at ``pos``: ``qI`` ``(..., J, Di)`` rotated, the token's own
        selector key ``kI`` ``(..., Di)`` (LayerNorm, then rotated; what
        the cache holds), and the heads' weights ``w`` ``(..., J)`` with
        the score's scale ``(J Di)^-1/2`` in them; float32."""
        lead = h.shape[:-1]
        nj, di = self.select["heads"], self.select["head_dim"]
        theta = float(self.rope["theta"])
        qi = rope(matmul(h, pi["wq"]).reshape(*lead, nj, di),
                  pos[..., None], theta)
        ki = matmul(h, pi["wk"])
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        ki = (ki - mu) * jax.lax.rsqrt(
            jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True) + self.epsilon)
        ki = (ki * pi["norm"]["gamma"].astype(jnp.float32)
              + pi["norm"]["beta"].astype(jnp.float32))
        ki = rope(ki[..., None, :], pos[..., None], theta)[..., 0, :]
        return qi, ki, matmul(h, pi["ww"]) * (nj * di) ** -0.5

    @staticmethod
    def index_scores(qi, w, ki, packed: int = 1):
        """``I[.., n, s] = sum_j w[.., n, j] relu(qI[.., n, j] . kI[.., s])``
        in float32: ``qi`` ``(..., n, J, Di)``, ``w`` ``(..., n, J)``,
        ``ki`` the cached selector keys in the cache's dtype, ``(..., t,
        Di)`` or, ``packed`` tokens a row as a pool holds them, ``(..., t /
        packed, packed x Di)``: the query then goes against each part of
        a row with zeros beside it, so that the rows are read as they lie
        and no key is moved. Returns ``(..., n, t)``."""
        if packed == 1:
            dots = _einsum("...njd,...td->...njt", qi, ki)
            return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=-2)
        di = qi.shape[-1]
        # (..., n, r, J, packed x Di): head j of part r lies in r's values
        parts = jnp.stack([
            jnp.pad(qi, [(0, 0)] * (qi.ndim - 1)
                    + [(r * di, (packed - 1 - r) * di)])
            for r in range(packed)], axis=-3)
        dots = _einsum("...nrjc,...tc->...nrjt", parts, ki)
        s = jnp.sum(jax.nn.relu(dots) * w[..., None, :, None], axis=-2)
        # (..., n, r, t / packed) -> position t' x packed + r
        return jnp.swapaxes(s, -1, -2).reshape(*s.shape[:-2], -1)

    def _attention_selected(self, p, x, pos, mask, attend):
        a = p["attn"]
        lead = x.shape[:-1]
        with jax.named_scope("attn/sparse"):
            h = rms_norm(x, p["ln1"]["gamma"], self.epsilon)
            q, k, v = self._qkv(a, h, pos)
        with jax.named_scope("attn/index"):
            index = self.index_inputs(a["index"], h, pos)
        if attend is not None:
            o = attend(q, k, v, index)
        else:
            cd = a["wk"].dtype  # as a served cache holds them
            with jax.named_scope("attn/index"):
                qi, ki, w = index
                scores = self.index_scores(qi, w, ki.astype(cd))
                b, n, t = scores.shape
                keep = select_mask(
                    scores.reshape(b * n, t),
                    jnp.broadcast_to(mask, scores.shape).reshape(b * n, t),
                    self.select["topk"]).reshape(b, n, t)
            with jax.named_scope("attn/sparse"):
                o = attend_dense(q, k.astype(cd), v.astype(cd), keep,
                                 self.softmax_scale)
        with jax.named_scope("attn/sparse"):
            return self._into(x, matmul(
                o.reshape(*lead, self.num_heads * self.head_dim), a["wo"]))

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
        if self.shared_width:
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
        return self._into(x, y.reshape(*lead, d)), picks

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
            "out_scale": self.out_scale, "qk_norm": self.qk_norm,
            "select": self.select,
            **({} if self.softmax_scale is None
               else {"softmax_scale": self.softmax_scale}),
            **({} if self.residual_scale == 1.0
               else {"residual_scale": self.residual_scale}),
        }
