"""Functional layer zoo.

Each layer is a declarative config object with two pure methods:

- ``init(rng, in_shape) -> (params, state, out_shape)``
- ``apply(params, state, x, train, rng) -> (y, new_state)``

``params`` are trainable (a dict pytree), ``state`` is non-trainable (e.g.
BatchNorm moving stats). Both are empty dicts for stateless layers. All apply
functions are jit-traceable with static shapes; convolutions use NHWC/HWIO
layouts so XLA tiles them onto the MXU directly.

Covers the builder vocabulary the reference examples use (reference:
examples/mnist.py — Dense/Conv2D/MaxPooling2D/Flatten/Dropout/Activation)
plus BatchNorm and pooling variants needed for the CIFAR/ResNet configs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------- activations

_ACTIVATIONS = {
    "linear": lambda x: x,
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
    "log_softmax": lambda x: jax.nn.log_softmax(x, axis=-1),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "elu": jax.nn.elu,
    "leaky_relu": jax.nn.leaky_relu,
}


def get_activation(name):
    if name is None:
        return _ACTIVATIONS["linear"]
    if callable(name):
        return name
    if name not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {name!r}")
    return _ACTIVATIONS[name]


# ------------------------------------------------------------------- registry

_LAYER_REGISTRY = {}


def register_layer(cls):
    _LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_from_config(cfg: dict):
    cfg = dict(cfg)
    name = cfg.pop("layer")
    if name not in _LAYER_REGISTRY:
        # the blocks that live beside this module register when imported:
        # a process that only loads a bundle has not imported them yet
        import distkeras_tpu.models.gqa_moe  # noqa: F401
        import distkeras_tpu.models.mamba2  # noqa: F401
        import distkeras_tpu.models.mla_moe  # noqa: F401
        import distkeras_tpu.parallel.expert_parallel  # noqa: F401
    return _LAYER_REGISTRY[name](**cfg)


# ----------------------------------------------------------------------- init


def _glorot_uniform(rng, shape, fan_in, fan_out, dtype=jnp.float32):
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


# ---------------------------------------------------------------------- base


class Layer:
    """Base declarative layer. Subclasses override init/apply/get_config."""

    # True for layers that consume an rng in train mode (Dropout) — the
    # pipeline trainer's block-run discovery excludes such blocks because
    # the GPipe schedule does not thread per-block rngs
    uses_train_rng = False

    def init(self, rng, in_shape):
        return {}, {}, in_shape

    def apply(self, params, state, x, train=False, rng=None):
        return x, state

    def get_config(self) -> dict:
        return {"layer": type(self).__name__}

    def sublayers(self):
        """Nested Layer children (composite layers override) — lets model
        walkers (e.g. ring-attention attachment) reach every layer."""
        return []

    def __repr__(self):
        cfg = {k: v for k, v in self.get_config().items() if k != "layer"}
        args = ", ".join(f"{k}={v!r}" for k, v in cfg.items())
        return f"{type(self).__name__}({args})"


# --------------------------------------------------------------------- layers


@register_layer
class Dense(Layer):
    """y = act(x @ W + b). Matmul-shaped for the MXU: keep units large/batched."""

    def __init__(self, units, activation=None, use_bias=True):
        self.units = int(units)
        self.activation = activation
        self.use_bias = bool(use_bias)

    def init(self, rng, in_shape):
        fan_in = in_shape[-1]
        params = {
            "kernel": _glorot_uniform(
                rng, (fan_in, self.units), fan_in, self.units
            )
        }
        if self.use_bias:
            params["bias"] = jnp.zeros((self.units,), jnp.float32)
        return params, {}, (*in_shape[:-1], self.units)

    def apply(self, params, state, x, train=False, rng=None):
        from distkeras_tpu.ops.quantization import qmatmul

        # qmatmul == plain matmul for f32 kernels; int8 weight-only when
        # the tree went through ops.quantization.quantize_params (serving)
        y = qmatmul(x, params["kernel"])
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return get_activation(self.activation)(y), state

    def get_config(self):
        return {
            "layer": "Dense",
            "units": self.units,
            "activation": self.activation,
            "use_bias": self.use_bias,
        }


@register_layer
class Conv2D(Layer):
    """NHWC conv, HWIO kernel — the layout XLA maps onto the MXU."""

    def __init__(
        self,
        filters,
        kernel_size,
        strides=1,
        padding="SAME",
        activation=None,
        use_bias=True,
    ):
        self.filters = int(filters)
        self.kernel_size = (
            (kernel_size, kernel_size)
            if isinstance(kernel_size, int)
            else tuple(kernel_size)
        )
        self.strides = (
            (strides, strides) if isinstance(strides, int) else tuple(strides)
        )
        self.padding = padding
        self.activation = activation
        self.use_bias = bool(use_bias)

    def init(self, rng, in_shape):
        kh, kw = self.kernel_size
        cin = in_shape[-1]
        fan_in = kh * kw * cin
        fan_out = kh * kw * self.filters
        params = {
            "kernel": _glorot_uniform(
                rng, (kh, kw, cin, self.filters), fan_in, fan_out
            )
        }
        if self.use_bias:
            params["bias"] = jnp.zeros((self.filters,), jnp.float32)
        out_shape = jax.eval_shape(
            lambda x, k: self._conv(x, k),
            jax.ShapeDtypeStruct((1, *in_shape), jnp.float32),
            jax.ShapeDtypeStruct(params["kernel"].shape, jnp.float32),
        ).shape[1:]
        return params, {}, out_shape

    def _conv(self, x, kernel):
        return lax.conv_general_dilated(
            x,
            kernel,
            window_strides=self.strides,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def apply(self, params, state, x, train=False, rng=None):
        y = self._conv(x, params["kernel"].astype(x.dtype))
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return get_activation(self.activation)(y), state

    def get_config(self):
        return {
            "layer": "Conv2D",
            "filters": self.filters,
            "kernel_size": list(self.kernel_size),
            "strides": list(self.strides),
            "padding": self.padding,
            "activation": self.activation,
            "use_bias": self.use_bias,
        }


class _Pool2D(Layer):
    def __init__(self, pool_size=2, strides=None, padding="VALID"):
        self.pool_size = (
            (pool_size, pool_size)
            if isinstance(pool_size, int)
            else tuple(pool_size)
        )
        strides = strides if strides is not None else self.pool_size
        self.strides = (
            (strides, strides) if isinstance(strides, int) else tuple(strides)
        )
        self.padding = padding

    def init(self, rng, in_shape):
        out = jax.eval_shape(
            lambda x: self.apply({}, {}, x)[0],
            jax.ShapeDtypeStruct((1, *in_shape), jnp.float32),
        ).shape[1:]
        return {}, {}, out

    def _window(self, x, init, op):
        return lax.reduce_window(
            x,
            init,
            op,
            window_dimensions=(1, *self.pool_size, 1),
            window_strides=(1, *self.strides, 1),
            padding=self.padding,
        )

    def get_config(self):
        return {
            "layer": type(self).__name__,
            "pool_size": list(self.pool_size),
            "strides": list(self.strides),
            "padding": self.padding,
        }


@register_layer
class MaxPool2D(_Pool2D):
    def apply(self, params, state, x, train=False, rng=None):
        return self._window(x, -jnp.inf, lax.max), state


@register_layer
class AvgPool2D(_Pool2D):
    def apply(self, params, state, x, train=False, rng=None):
        s = self._window(x, 0.0, lax.add)
        return s / (self.pool_size[0] * self.pool_size[1]), state


@register_layer
class GlobalAvgPool2D(Layer):
    def init(self, rng, in_shape):
        return {}, {}, (in_shape[-1],)

    def apply(self, params, state, x, train=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), state


@register_layer
class Flatten(Layer):
    def init(self, rng, in_shape):
        size = 1
        for d in in_shape:
            size *= d
        return {}, {}, (size,)

    def apply(self, params, state, x, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@register_layer
class Dropout(Layer):
    """Inverted dropout; identity in eval mode. Needs an rng when train=True."""

    uses_train_rng = True

    def __init__(self, rate):
        self.rate = float(rate)

    def apply(self, params, state, x, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError("Dropout.apply(train=True) requires an rng")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state

    def get_config(self):
        return {"layer": "Dropout", "rate": self.rate}


@register_layer
class Activation(Layer):
    def __init__(self, activation):
        self.activation = activation

    def apply(self, params, state, x, train=False, rng=None):
        return get_activation(self.activation)(x), state

    def get_config(self):
        return {"layer": "Activation", "activation": self.activation}


@register_layer
class Embedding(Layer):
    """Token embedding (+ optional learned positions) for (B, T) int ids.

    No reference counterpart (the reference has no sequence workloads,
    SURVEY §5.7); the entry layer of the rebuild's transformer family.
    ``multiplier``: what a token's row is multiplied by as it enters the
    residual stream (1: nothing).
    """

    def __init__(self, vocab_size, dim, with_positions=True, multiplier=1.0):
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.with_positions = bool(with_positions)
        self.multiplier = float(multiplier)

    def init(self, rng, in_shape):
        (t,) = in_shape
        k1, k2 = jax.random.split(rng)
        params = {
            "tokens": 0.02
            * jax.random.normal(k1, (self.vocab_size, self.dim), jnp.float32)
        }
        if self.with_positions:
            params["positions"] = 0.02 * jax.random.normal(
                k2, (t, self.dim), jnp.float32
            )
        return params, {}, (t, self.dim)

    def apply(self, params, state, x, train=False, rng=None):
        y = params["tokens"][x.astype(jnp.int32)]
        if self.multiplier != 1.0:
            y = y * self.multiplier
        if self.with_positions:
            y = y + params["positions"][None, : y.shape[1]]
        return y, state

    def get_config(self):
        return {
            "layer": "Embedding",
            "vocab_size": self.vocab_size,
            "dim": self.dim,
            "with_positions": self.with_positions,
            **({} if self.multiplier == 1.0
               else {"multiplier": self.multiplier}),
        }


@register_layer
class TiedHead(Layer):
    """The output head of a model whose head IS its embedding: ``logits =
    x E^T / logits_scaling`` with ``E`` the ``tokens`` table of layer
    ``tied_to`` (the ``Embedding``). It has no parameters of its own:
    ``Model.apply`` and the serving programs hand it that layer's
    (``params_of``), so the table is held, and read by a step, once."""

    def __init__(self, vocab_size, logits_scaling=1.0, tied_to=0):
        self.vocab_size = int(vocab_size)
        self.logits_scaling = float(logits_scaling)
        self.params_of = int(tied_to)

    def init(self, rng, in_shape):
        return {}, {}, (*in_shape[:-1], self.vocab_size)

    def logits(self, p_emb, x):
        """``x`` ``(..., d)`` against the table ``(V, d)``: both operands in
        the table's dtype, float32 out (``models.mla_moe._einsum``)."""
        from distkeras_tpu.models.mla_moe import _einsum

        y = _einsum("...d,vd->...v", x, p_emb["tokens"])
        return y if self.logits_scaling == 1.0 else y / self.logits_scaling

    def apply(self, params, state, x, train=False, rng=None):
        return self.logits(params, x), state

    def get_config(self):
        return {"layer": "TiedHead", "vocab_size": self.vocab_size,
                "logits_scaling": self.logits_scaling,
                "tied_to": self.params_of}


@register_layer
class LayerNorm(Layer):
    """Normalize over the trailing feature axis with learned scale/shift.

    ``norm_fn`` is a process-local hook (same contract as
    ``MultiHeadSelfAttention.attention_fn``): point it at
    ``ops.fused_layernorm.fused_layer_norm`` to run the one-pass Pallas
    kernel instead of the three-pass XLA path. Not serialized — a
    deserialized layer computes the plain path until the receiving
    process re-attaches the hook."""

    def __init__(self, epsilon=1e-5):
        self.epsilon = float(epsilon)
        self.norm_fn = None  # override to plug in the fused kernel

    def init(self, rng, in_shape):
        d = in_shape[-1]
        return (
            {"gamma": jnp.ones((d,), jnp.float32),
             "beta": jnp.zeros((d,), jnp.float32)},
            {},
            in_shape,
        )

    def apply(self, params, state, x, train=False, rng=None):
        if self.norm_fn is not None:
            y = self.norm_fn(x, params["gamma"], params["beta"], self.epsilon)
            return y, state
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * lax.rsqrt(var + self.epsilon)
        y = y * params["gamma"] + params["beta"]
        return y.astype(x.dtype), state

    def get_config(self):
        if self.norm_fn is not None:
            import logging

            logging.getLogger(__name__).warning(
                "LayerNorm.norm_fn is process-local and is not serialized; "
                "the deserialized layer will use the plain XLA path until "
                "the fused kernel is re-attached"
            )
        return {"layer": "LayerNorm", "epsilon": self.epsilon}


@register_layer
class GlobalAvgPool1D(Layer):
    """(B, T, D) -> (B, D): mean over the sequence axis."""

    def init(self, rng, in_shape):
        t, d = in_shape
        return {}, {}, (d,)

    def apply(self, params, state, x, train=False, rng=None):
        return jnp.mean(x, axis=1), state


def cache_attention(q, keys, values, mask):
    """Softmax attention of ``q`` over cached ``keys`` / ``values``
    ``(B, T, H, Dh)``, in the cache's dtype with float32 accumulation:
    THE masked softmax of every program that attends a cache it has
    gathered or holds whole. ``q`` ``(B, H, Dh)`` is one token a row
    under ``mask`` ``(B, T)``; ``q`` ``(B, C, H, Dh)`` a chunk under
    ``(C, T)`` (every row alike) or ``(B, C, T)``. True = may attend."""
    c = "c" if q.ndim == 4 else ""
    scores = jnp.einsum(f"b{c}hd,bthd->bh{c}t", q, keys)
    scores = scores / np.sqrt(q.shape[-1])
    mask = mask[None, None] if mask.ndim < q.ndim - 1 else mask[:, None]
    w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum(f"bh{c}t,bthd->b{c}hd", w, values)


@register_layer
class MultiHeadSelfAttention(Layer):
    """Multi-head self-attention over (batch, seq, features).

    No reference counterpart (SURVEY §5.7: the reference has no attention);
    this is the long-context building block of the TPU rebuild. On one chip
    it computes dense softmax attention; for sequences sharded across a mesh
    the same math is served by ``parallel.ring_attention.ring_attention``
    (set ``layer.attention_fn`` or use the functional API), which rotates
    K/V blocks over ICI with an online softmax.

    ``attention_fn`` is a process-local hook: it closes over a live Mesh, so
    it is intentionally NOT part of ``get_config`` and does not survive
    serialize_model / from_config — a deserialized layer computes dense
    attention until the receiving process re-attaches its own mesh hook
    (get_config warns when a hook would be dropped).
    """

    def __init__(self, num_heads, head_dim=None, causal=False, use_bias=True):
        self.num_heads = int(num_heads)
        self.head_dim = None if head_dim is None else int(head_dim)
        self.causal = bool(causal)
        self.use_bias = bool(use_bias)
        self.attention_fn = None  # override to plug in ring attention

    def init(self, rng, in_shape):
        t, d = in_shape[-2], in_shape[-1]
        hd = self.head_dim or d // self.num_heads
        if self.head_dim is None and d % self.num_heads:
            raise ValueError(
                f"features {d} not divisible by num_heads {self.num_heads}"
            )
        inner = self.num_heads * hd
        ks = jax.random.split(rng, 4)
        params = {
            name: _glorot_uniform(k, shape, shape[0], shape[1])
            for name, k, shape in [
                ("wq", ks[0], (d, inner)),
                ("wk", ks[1], (d, inner)),
                ("wv", ks[2], (d, inner)),
                ("wo", ks[3], (inner, d)),
            ]
        }
        if self.use_bias:
            params["bo"] = jnp.zeros((d,), jnp.float32)
        return params, {}, (*in_shape[:-1], d)

    def forward(self, params, x, attend):
        """``x`` ``(..., d)``: project q, k and v to ``(..., H, Dh)``,
        ``attend(q, k_new, v_new) -> o (..., H, Dh)``, then ``wo`` and
        the bias (a layer built with ``use_bias`` is one whose params
        hold ``"bo"``). THE attention arithmetic of every program: what
        the new keys and values are attended with and where they are
        kept is the caller's ``attend``. Dtypes: the projections and
        the ``wo`` product are in their input's dtype (``qmatmul``), so
        the output is in ``attend``'s output dtype, and the bias is
        cast to it: a float32 ``"bo"`` beside bfloat16 activations adds
        in bfloat16 and never promotes the residual branch."""
        from distkeras_tpu.ops.quantization import qmatmul, qshape

        h = self.num_heads
        hd = qshape(params["wq"])[1] // h

        def proj(w):
            return qmatmul(x, w).reshape(*x.shape[:-1], h, hd)

        q, k, v = proj(params["wq"]), proj(params["wk"]), proj(params["wv"])
        o = attend(q, k, v)
        o = qmatmul(o.reshape(*x.shape[:-1], h * hd), params["wo"])
        if "bo" in params:
            o = o + params["bo"].astype(o.dtype)
        return o

    def attend_self(self, q, k, v):
        """The tokens' own keys and values, nothing cached: ``attend``
        of a full forward (training, scoring)."""
        from distkeras_tpu.parallel.ring_attention import dense_attention

        attn = self.attention_fn or dense_attention
        return attn(q, k, v, causal=self.causal)

    def apply(self, params, state, x, train=False, rng=None):
        return self.forward(params, x, self.attend_self), state

    def get_config(self):
        if self.attention_fn is not None:
            import logging

            logging.getLogger(__name__).warning(
                "MultiHeadSelfAttention.attention_fn is process-local and is "
                "not serialized; the deserialized layer will use dense "
                "attention until a mesh hook is re-attached"
            )
        return {
            "layer": "MultiHeadSelfAttention",
            "num_heads": self.num_heads,
            "head_dim": self.head_dim,
            "causal": self.causal,
            "use_bias": self.use_bias,
        }


@register_layer
class TransformerBlock(Layer):
    """Pre-LN transformer block: x + MHSA(LN(x)), then x + MLP(LN(x)).

    The MLP is Dense(mlp_ratio*d, gelu) -> Dense(d). Composes the rebuild's
    long-context vocabulary: with ``parallel.ring_attention`` attached to
    the inner attention (see ``attach_ring_attention``) the block runs with
    the sequence axis sharded over a mesh.

    ``remat=True`` wraps the block in ``jax.checkpoint``: the backward pass
    recomputes the block's activations instead of holding them through the
    whole forward — activation memory drops from O(depth) blocks to O(1)
    per block at ~1/3 extra FLOPs, the standard TPU HBM<->FLOPs trade that
    makes deep/long-sequence training fit. Numerics are unchanged (pinned
    by test). No reference counterpart (the reference has no attention and
    delegates memory to the Keras backend).

    ``dropout`` applies inverted residual dropout to the attention and MLP
    branch outputs in train mode (identity in eval; rng required when
    live). A dropout block consumes the train rng, so pipeline towers
    exclude it (``uses_train_rng``).
    """

    def __init__(self, num_heads, mlp_ratio=4, causal=False, remat=False,
                 dropout=0.0):
        self.num_heads = int(num_heads)
        self.mlp_ratio = int(mlp_ratio)
        self.causal = bool(causal)
        self.remat = bool(remat)
        self.dropout = float(dropout)
        # rng-consuming blocks are excluded from pipeline towers
        # (trainers._find_block_run) — declare only when dropout is live
        self.uses_train_rng = self.dropout > 0.0
        self.mhsa = MultiHeadSelfAttention(self.num_heads, causal=self.causal)
        self.ln1 = LayerNorm()
        self.ln2 = LayerNorm()
        self._fc1 = None  # built in init (needs d)
        self._fc2 = None

    def sublayers(self):
        parts = [self.mhsa, self.ln1, self.ln2]
        if self._fc1 is not None:
            parts += [self._fc1, self._fc2]
        return parts

    def init(self, rng, in_shape):
        t, d = in_shape
        self._fc1 = Dense(self.mlp_ratio * d, activation="gelu")
        self._fc2 = Dense(d)
        ks = jax.random.split(rng, 5)
        params, state = {}, {}
        for name, layer, k, shape in [
            ("ln1", self.ln1, ks[0], in_shape),
            ("mhsa", self.mhsa, ks[1], in_shape),
            ("ln2", self.ln2, ks[2], in_shape),
            ("fc1", self._fc1, ks[3], in_shape),
        ]:
            p, s, out_shape = layer.init(k, shape)
            params[name], state[name] = p, s
        p, s, _ = self._fc2.init(ks[4], (t, self.mlp_ratio * d))
        params["fc2"], state["fc2"] = p, s
        return params, state, in_shape

    def apply(self, params, state, x, train=False, rng=None):
        if self.remat:
            import functools

            fn = jax.checkpoint(functools.partial(self._apply, train=train))
            return fn(params, state, x, rng)
        return self._apply(params, state, x, rng, train=train)

    def forward(self, p, x, attend, branch=None):
        """The block's arithmetic, once: ``x`` ``(..., d)`` through
        ``ln1``, the q/k/v projections, ``attend(q, k_new, v_new) -> o``
        (each ``(..., H, Dh)``), ``wo`` and its bias, the residual,
        ``ln2``, ``fc1``, ``fc2``, the residual. ``attend`` owns what a
        caller knows and the block does not: where the new keys and
        values are written, which rows are frozen, what is gathered or
        read in place, the mask, the softmax or the kernel; a caller
        that keeps a cache gets it back by its closure's side.
        ``branch(h, i)`` (training's dropout) maps the output of residual
        branch ``i`` before it is added."""
        h, _ = self.ln1.apply(p["ln1"], {}, x)
        h = self.mhsa.forward(p["mhsa"], h, attend)
        x = x + (h if branch is None else branch(h, 0))
        h, _ = self.ln2.apply(p["ln2"], {}, x)
        h, _ = self._fc1.apply(p["fc1"], {}, h)
        h, _ = self._fc2.apply(p["fc2"], {}, h)
        return x + (h if branch is None else branch(h, 1))

    def _apply(self, params, state, x, rng, train=False):
        branch = None
        if train and self.dropout > 0.0:
            if rng is None:
                raise ValueError(
                    "TransformerBlock(dropout>0).apply(train=True) "
                    "requires an rng"
                )
            keys = tuple(jax.random.split(rng))
            # reuse the Dropout layer's mask logic (stateless, param-free)
            # so the two inverted-dropout implementations cannot drift
            dropper = Dropout(self.dropout)

            def branch(h, i):
                return dropper.apply({}, {}, h, train=True, rng=keys[i])[0]

        y = self.forward(params, x, self.mhsa.attend_self, branch)
        return y, dict(state)  # every sublayer is stateless

    def get_config(self):
        return {
            "layer": "TransformerBlock",
            "num_heads": self.num_heads,
            "mlp_ratio": self.mlp_ratio,
            "causal": self.causal,
            "remat": self.remat,
            "dropout": self.dropout,
        }


@register_layer
class BatchNorm(Layer):
    """Batch normalization over all but the channel axis.

    Train mode normalizes with batch statistics and updates moving stats in
    ``state``; eval mode uses the moving stats. Functional state threading —
    no in-place mutation — keeps this jit/shard_map-safe. Under the sync
    data-parallel trainer the whole step is one jitted program over a GSPMD-
    sharded batch, so ``jnp.mean``/``jnp.var`` here reduce over the GLOBAL
    batch — XLA inserts the cross-device collective — and every replica holds
    identical moving stats (sync-BatchNorm semantics; pinned by
    tests/test_trainers_sync.py::test_sync_batchnorm_global_batch_stats).
    """

    def __init__(self, momentum=0.99, epsilon=1e-5, scale=True, center=True):
        self.momentum = float(momentum)
        self.epsilon = float(epsilon)
        self.scale = bool(scale)
        self.center = bool(center)

    def init(self, rng, in_shape):
        c = in_shape[-1]
        params = {}
        if self.scale:
            params["gamma"] = jnp.ones((c,), jnp.float32)
        if self.center:
            params["beta"] = jnp.zeros((c,), jnp.float32)
        state = {
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32),
        }
        return params, state, in_shape

    def apply(self, params, state, x, train=False, rng=None):
        axes = tuple(range(x.ndim - 1))
        if train:
            mean = jnp.mean(x.astype(jnp.float32), axis=axes)
            var = jnp.var(x.astype(jnp.float32), axis=axes)
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean,
                "var": m * state["var"] + (1 - m) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.epsilon)
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
        if self.scale:
            y = y * params["gamma"].astype(x.dtype)
        if self.center:
            y = y + params["beta"].astype(x.dtype)
        return y, new_state

    def get_config(self):
        return {
            "layer": "BatchNorm",
            "momentum": self.momentum,
            "epsilon": self.epsilon,
            "scale": self.scale,
            "center": self.center,
        }
