"""The Mamba-2 block (a state-space mixer, then a gated MLP), written once.

A layer whose memory is NOT rows a token: whatever the sequence's length it
keeps, a sequence, one state of ``(H, P, N)`` values and the last ``K - 1``
inputs of its convolution. ``Mamba2Block.forward(p, x, carry, ...)`` is the
layer's arithmetic for all of its uses, with the memory handed in and back:

- ``apply`` (``Sequential.apply``, ``eval_shape``, the CPU tests): the whole
  sequence from a zero state, by the chunk form;
- the serving engine's prefill-chunk program: a chunk of one sequence's
  tokens from the state and tail the slot holds (zeros at position 0), which
  a chunk's padding does not advance (``n_valid``);
- the serving engine's decode-step program: one token a slot
  (``step=True``); a slot that is not decoding keeps its state (``keep``).

A layer is, with ``u = RMSNorm(x)`` and ``r`` the residual multiplier::

    [z | xBC | dt] = u W_in            (widths H P, H P + 2 N, H; no bias)
    xBC_t = silu(b + sum_j w[j] * xBC_{t-(K-1)+j})   (depthwise, causal,
            zeros before the sequence's start), split [x (H, P) | B (N) | C (N)]
    D_t = softplus(dt_t + dt_bias) (H,);  a_t = exp(D_t A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + D_t (x_t (x) B_t)   a head (P, N);  S_{-1} = 0
    y_t = S_t C_t + D x_t
    y = RMSNorm(y * silu(z); gn) over all H P values (the gate before the
        norm, one group);  x = x + r * (y W_out)
    x = x + r * (silu(v Wg) * (v Wu)) Wd,  v = RMSNorm(x)

One group of ``B`` and ``C`` serves every head (``n_groups`` 1). The chunk
form takes blocks of ``L <= chunk`` positions: with ``s_t`` the running sum
of ``D_i A`` inside a block, ::

    y_t = sum_{u<=t} exp(s_t - s_u) (C_t . B_u) D_u x_u
          + exp(s_t) S_prev C_t + D x_t
    S_next = exp(s_L) S_prev + sum_u exp(s_L - s_u) D_u x_u (x) B_u

products of ``L x L`` and ``L x N`` blocks and no loop over tokens; an
algorithm for the same numbers as the recurrence. Precision: the
projections and the MLP take both operands in the weights' dtype and
accumulate in float32 (``mla_moe.matmul``); everything that makes, updates
or reads the state takes float32 operands at ``HIGHEST`` (element-wise work
and small contractions, bound by bytes and not by the MXU). The state is
held in float32 (``Mamba2Block.state_dtype``, an attribute of the class and
no option of the block, the zoo entry or a bundle: a state rounded to
bfloat16 at every step, over the hundreds of steps a state remembers, is
another result, 15-18% faster on the chip, and one that the benchmark's
comparison of served tokens cannot tell from this one (``PERF.md`` section
2); a test sets the attribute to show what it does), the convolution's tail
always in float32 (rounded, it would give the step other
convolution inputs than the chunk saw).

What the block caches it says itself, and the serving engine reads it from
the block and from no model's name: ``kind`` ``"ssm"``, ``cached_rows`` 0
(nothing a token), ``slot_state`` (the shapes and dtypes of what a slot
holds whatever its length).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.layers import Layer, register_layer
from distkeras_tpu.models.mla_moe import _normal, gated_mlp, matmul, rms_norm

_HIGHEST = jax.lax.Precision.HIGHEST


def _f32(spec, *operands):
    """``einsum`` of float32 operands at ``HIGHEST``: what touches the state."""
    return jnp.einsum(spec, *(o.astype(jnp.float32) for o in operands),
                      precision=_HIGHEST)


def causal_conv(raw, tail, w, b, n_valid=None):
    """The depthwise causal convolution over ``raw`` ``(B, n, C)`` with the
    ``K - 1`` inputs before it in ``tail`` ``(B, K - 1, C)``; ``w`` ``(K,
    C)``, ``b`` ``(C,)``. Returns ``(silu(b + sum_j w[j] ext[t + j]), the
    new tail)``: the inputs of the last ``K - 1`` of the first ``n_valid``
    positions (None: all ``n``), so that what lies behind a chunk's real
    tokens is never the next chunk's past."""
    k, n = w.shape[0], raw.shape[1]
    ext = jnp.concatenate([tail.astype(jnp.float32), raw], axis=1)
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32) + sum(
        wf[j] * jax.lax.slice_in_dim(ext, j, j + n, axis=1) for j in range(k))
    at = n if n_valid is None else n_valid
    new_tail = jax.lax.dynamic_slice_in_dim(ext, at, k - 1, axis=1)
    return jax.nn.silu(out), new_tail


def ssm_step(state, x, bm, cm, dt, a, d_skip):
    """One position of the recurrence for ``B`` sequences: ``state`` ``(B,
    H, P, N)``, ``x`` ``(B, H, P)``, ``bm``/``cm`` ``(B, N)``, ``dt`` ``(B,
    H)`` (after the softplus), ``a`` ``(H,)`` negative, ``d_skip`` ``(H,)``.
    Returns ``(y (B, H, P), the new state in the state's dtype)``; ``y``
    reads the state as it is held."""
    decay = jnp.exp(dt * a)  # (B, H)
    new = (decay[..., None, None] * state.astype(jnp.float32)
           + (dt[..., None] * x)[..., None] * bm[:, None, None, :])
    new = new.astype(state.dtype)
    y = _f32("bhpn,bn->bhp", new, cm) + d_skip[:, None] * x
    return y, new


def ssm_chunk(state, x, bm, cm, dt, a, d_skip, chunk: int):
    """``n`` positions of the same recurrence by the chunk form, from
    ``state`` ``(B, H, P, N)``: ``x`` ``(B, n, H, P)``, ``bm``/``cm`` ``(B,
    n, N)``, ``dt`` ``(B, n, H)`` (0 where a position does not exist: it
    then leaves the state as it was). Blocks of ``L = min(chunk, n)``
    positions, one after another (``n`` a multiple of ``L``). Returns ``(y
    (B, n, H, P), the state after the last position, float32)``."""
    b, n, nh, hp = x.shape
    size = min(int(chunk), n)
    if n % size:
        raise ValueError(f"{n} positions are not whole blocks of {size}")
    nb = n // size
    lower = jnp.tril(jnp.ones((size, size), jnp.float32))  # [t, u]: u <= t

    def blocks(v, heads_first=False):  # (B, n, ...) -> (nb, B, [H,] L, ...)
        v = jnp.moveaxis(v.reshape(b, nb, size, *v.shape[2:]), 1, 0)
        return jnp.moveaxis(v, 3, 2) if heads_first else v

    def one(s_prev, blk):
        xb, bb, cb, db = blk  # (B, H, L, P), (B, L, N), (B, L, N), (B, H, L)
        # the running sum of D_i A inside the block, as a product with a
        # triangle of ones (a long cumsum costs the TPU's compiler seconds)
        s = _f32("tu,bhu->bht", lower, db * a[:, None])  # (B, H, L), <= 0
        gap = s[..., :, None] - s[..., None, :]  # [b, h, t, u]
        decay = jnp.exp(jnp.where(lower > 0, gap, -jnp.inf))
        g = _f32("btn,bun->btu", cb, bb)
        w = decay * g[:, None] * db[:, :, None, :]  # [b, h, t, u]
        y = _f32("bhtu,bhup->bhtp", w, xb)
        y = y + jnp.exp(s)[..., None] * _f32("bhpn,btn->bhtp", s_prev, cb)
        last = s[..., -1]  # (B, H)
        carry = db * jnp.exp(last[..., None] - s)  # (B, H, L)
        s_next = jnp.exp(last)[..., None, None] * s_prev + _f32(
            "bhup,bun->bhpn", xb * carry[..., None], bb)
        return s_next, y + d_skip[:, None, None] * xb

    s_last, ys = jax.lax.scan(
        one, state.astype(jnp.float32),
        (blocks(x, True), blocks(bm), blocks(cm), blocks(dt, True)))
    # (nb, B, H, L, P) -> (B, nb, L, H, P)
    return ys.transpose(1, 0, 3, 2, 4).reshape(b, n, nh, hp), s_last


@register_layer
class Mamba2Block(Layer):
    """One pre-RMSNorm Mamba-2 layer: the mixer (``num_heads`` heads of
    ``head_dim``, a state of ``state_dim`` a head value, a causal depthwise
    convolution of ``conv_width`` over ``x``, ``B`` and ``C``, blocks of
    ``chunk`` positions in the chunk form), then a gated MLP of
    ``ffn_width``; each branch times ``residual_scale`` into the residual
    stream."""

    kind = "ssm"
    state_dtype = "float32"  # what the state is held in between calls
    causal = True
    cached_rows = 0  # nothing a token: a state and a tail a sequence
    # the most tokens one prefill-chunk program takes (the chunk form goes
    # ``chunk`` positions at a time whatever the program's length), and so,
    # a sixteenth of it, the fewest a chunk program is built for: every
    # chunk reads every weight, so a short one costs what 64 tokens cost
    chunk_tokens = 1024
    _std = 0.02

    def __init__(self, num_heads, head_dim, state_dim, ffn_width,
                 n_groups=1, conv_width=4, chunk=256, epsilon=1e-5,
                 residual_scale=1.0, out_scale=1.0):
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.state_dim = int(state_dim)
        self.ffn_width = int(ffn_width)
        self.n_groups = int(n_groups)
        self.conv_width = int(conv_width)
        self.chunk = int(chunk)
        self.epsilon = float(epsilon)
        self.residual_scale = float(residual_scale)
        self.out_scale = float(out_scale)
        if self.n_groups != 1:
            raise ValueError(
                f"n_groups {self.n_groups}: one group of B and C serves "
                f"every head here")
        if min(self.num_heads, self.head_dim, self.state_dim, self.ffn_width,
               self.chunk) < 1 or self.conv_width < 2:
            raise ValueError("a Mamba-2 block needs sizes >= 1 and a "
                             "convolution of 2 or more")

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.state_dim

    @property
    def slot_state(self) -> tuple:
        """``((shape, dtype), ...)`` of what a sequence holds whatever its
        length: the state, and the convolution's tail (float32 always)."""
        return (
            ((self.num_heads, self.head_dim, self.state_dim),
             jnp.dtype(self.state_dtype)),
            ((self.conv_width - 1, self.conv_dim), jnp.dtype(jnp.float32)),
        )

    def zero_carry(self, batch: int):
        return tuple(jnp.zeros((batch, *shape), dt)
                     for shape, dt in self.slot_state)

    def init(self, rng, in_shape):
        d = in_shape[-1]
        ks = iter(jax.random.split(rng, 10))
        std, dt = self._std, jnp.float32
        nh, di, n = self.num_heads, self.inner, self.state_dim
        half = 0.5  # 1 / sqrt(conv_width) at a width of 4, as Conv1d's own
        step = jnp.exp(jax.random.uniform(
            next(ks), (nh,), dt, np.log(1e-3), np.log(1e-1)))
        mixer = {
            "w_in": _normal(next(ks), (d, 2 * di + 2 * n + nh), std, dt),
            "conv_w": jax.random.uniform(
                next(ks), (self.conv_width, self.conv_dim), dt, -half, half),
            "conv_b": jax.random.uniform(
                next(ks), (self.conv_dim,), dt, -half, half),
            # the inverse softplus of a log-uniform step in [1e-3, 1e-1]
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(jax.random.uniform(
                next(ks), (nh,), dt, 1.0, 16.0)),
            "d_skip": jnp.ones((nh,), dt),
            "norm": {"gamma": jnp.ones((di,), dt)},
            "w_out": _normal(next(ks), (di, d), std * self.out_scale, dt),
        }
        ffn = {"wg": _normal(next(ks), (d, self.ffn_width), std, dt),
               "wu": _normal(next(ks), (d, self.ffn_width), std, dt),
               "wd": _normal(next(ks), (self.ffn_width, d),
                             std * self.out_scale, dt)}
        return ({"ln1": {"gamma": jnp.ones((d,), dt)}, "mixer": mixer,
                 "ln2": {"gamma": jnp.ones((d,), dt)}, "ffn": ffn},
                {}, in_shape)

    # -- the arithmetic, once -----------------------------------------------

    def _split(self, m, h):
        """``z``, raw ``xBC`` and ``dt`` (before the bias) of the normed
        input ``h`` ``(..., d)``, float32."""
        di = self.inner
        zxd = matmul(h, m["w_in"])
        return (zxd[..., :di], zxd[..., di:di + self.conv_dim],
                zxd[..., di + self.conv_dim:])

    def _inputs(self, m, xbc, dt):
        """The recurrence's inputs of the convolved ``xbc`` ``(..., C)``
        and the raw ``dt`` ``(..., H)``: ``x`` ``(..., H, P)``, ``B``,
        ``C`` ``(..., N)``, the step ``D`` ``(..., H)``, ``A`` ``(H,)``."""
        di, n = self.inner, self.state_dim
        x = xbc[..., :di].reshape(*xbc.shape[:-1], self.num_heads,
                                  self.head_dim)
        step = jax.nn.softplus(dt + m["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(m["a_log"].astype(jnp.float32))
        return x, xbc[..., di:di + n], xbc[..., di + n:], step, a

    def _out(self, m, y, z):
        """The gate before the norm, the norm over all ``H P`` values,
        the output projection."""
        gated = y.reshape(*z.shape) * jax.nn.silu(z)
        return matmul(rms_norm(gated, m["norm"]["gamma"], self.epsilon),
                      m["w_out"])

    def mix(self, m, h, carry, n_valid=None):
        """The mixer over ``h`` ``(B, n, d)`` (normed) from ``carry``
        ``(state (B, H, P, N), tail (B, K - 1, C))``; of the ``n`` positions
        the first ``n_valid`` exist (None: all), the rest advance nothing.
        Returns ``(y (B, n, d) float32, the carry after them)``."""
        state, tail = carry
        b, n, _ = h.shape
        size = min(self.chunk, n)
        pad = -n % size
        with jax.named_scope("ssm/proj"):
            z, raw, dt = self._split(m, h)
            xbc, tail = causal_conv(raw, tail, m["conv_w"], m["conv_b"],
                                    n_valid)
            x, bm, cm, step, a = self._inputs(m, xbc, dt)
        with jax.named_scope("ssm/scan"):
            if n_valid is not None:
                step = jnp.where(
                    (jnp.arange(n) < n_valid)[None, :, None], step, 0.0)
            if pad:  # positions that do not exist, behind the sequence
                x, bm, cm, step = (
                    jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                    for v in (x, bm, cm, step))
            y, last = ssm_chunk(
                state, x, bm, cm, step, a,
                m["d_skip"].astype(jnp.float32), self.chunk)
            y, last = y[:, :n], last.astype(state.dtype)
        with jax.named_scope("ssm/proj"):
            return self._out(m, y, z), (last, tail)

    def mix_step(self, m, h, carry, keep=None):
        """The mixer for one token a sequence: ``h`` ``(B, d)``; a sequence
        where ``keep`` ``(B,)`` is False keeps its state and its tail as
        they were. Returns ``(y (B, d) float32, the carry)``."""
        state, tail = carry
        with jax.named_scope("ssm/proj"):
            z, raw, dt = self._split(m, h)
            xbc, new_tail = causal_conv(
                raw[:, None], tail, m["conv_w"], m["conv_b"])
            x, bm, cm, step, a = self._inputs(m, xbc[:, 0], dt)
        with jax.named_scope("ssm/update"):
            if keep is not None:
                # a step of 0 leaves a state as it was, bit for bit
                step = jnp.where(keep[:, None], step, 0.0)
                new_tail = jnp.where(keep[:, None, None], new_tail, tail)
            y, new = ssm_step(state, x, bm, cm, step, a,
                              m["d_skip"].astype(jnp.float32))
        with jax.named_scope("ssm/proj"):
            return self._out(m, y, z), (new, new_tail)

    def forward(self, p, x, carry, n_valid=None, keep=None, step=False):
        """``x`` ``(B, n, d)``, or with ``step`` ``(B, d)``: the layer from
        ``carry``. Returns ``(y float32, the carry after it)``."""
        x = x.astype(jnp.float32)
        r = self.residual_scale
        h = rms_norm(x, p["ln1"]["gamma"], self.epsilon)
        if step:
            y, carry = self.mix_step(p["mixer"], h, carry, keep)
        else:
            y, carry = self.mix(p["mixer"], h, carry, n_valid)
        x = x + r * y
        u = rms_norm(x, p["ln2"]["gamma"], self.epsilon)
        with jax.named_scope("ffn/dense"):
            return x + r * gated_mlp(p["ffn"], u), carry

    def apply(self, params, state, x, train=False, rng=None):
        y, _ = self.forward(params, x, self.zero_carry(x.shape[0]))
        return y, state

    def get_config(self):
        return {
            "layer": "Mamba2Block", "num_heads": self.num_heads,
            "head_dim": self.head_dim, "state_dim": self.state_dim,
            "ffn_width": self.ffn_width, "n_groups": self.n_groups,
            "conv_width": self.conv_width, "chunk": self.chunk,
            "epsilon": self.epsilon, "residual_scale": self.residual_scale,
            "out_scale": self.out_scale,
        }
