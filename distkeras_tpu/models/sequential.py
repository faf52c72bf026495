"""Sequential model container + Residual composite block.

Keras-like surface (reference: examples/mnist.py builds
``keras.models.Sequential`` and the trainers carry it around serialized;
reference: distkeras/utils.py -> serialize_keras_model). A ``Sequential``
here is a declarative layer list that, once ``build(input_shape)`` is called,
exposes:

- ``model.params`` / ``model.state`` — pytrees (dicts keyed "0", "1", ...)
- ``model.apply(params, state, x, train, rng) -> (y, new_state)`` — a pure
  function safe to close over in jit/grad/shard_map
- ``get_weights()/set_weights()`` — flat ndarray lists, the reference's wire
  format for PS pull/commit payloads

``Residual`` adds the skip-connection vocabulary needed for ResNet-18
(BASELINE config 5) while staying inside the declarative-config world.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.layers import (
    Layer,
    get_activation,
    layer_from_config,
    register_layer,
)


@register_layer
class Residual(Layer):
    """y = act(main(x) + shortcut(x)); shortcut defaults to identity."""

    def __init__(self, layers, shortcut=None, activation="relu"):
        self.layers = [
            l if isinstance(l, Layer) else layer_from_config(l) for l in layers
        ]
        self.shortcut = [
            l if isinstance(l, Layer) else layer_from_config(l)
            for l in (shortcut or [])
        ]
        self.activation = activation

    def init(self, rng, in_shape):
        params, state = {}, {}
        shape = in_shape
        rngs = jax.random.split(rng, len(self.layers) + len(self.shortcut) + 1)
        for i, layer in enumerate(self.layers):
            p, s, shape = layer.init(rngs[i], shape)
            params[f"main_{i}"] = p
            state[f"main_{i}"] = s
        sshape = in_shape
        for i, layer in enumerate(self.shortcut):
            p, s, sshape = layer.init(rngs[len(self.layers) + i], sshape)
            params[f"short_{i}"] = p
            state[f"short_{i}"] = s
        if sshape != shape:
            raise ValueError(
                f"Residual branch shapes differ: main {shape} vs shortcut {sshape}"
            )
        return params, state, shape

    def apply(self, params, state, x, train=False, rng=None):
        rngs = (
            jax.random.split(rng, len(self.layers) + len(self.shortcut))
            if rng is not None
            else [None] * (len(self.layers) + len(self.shortcut))
        )
        new_state = {}
        y = x
        for i, layer in enumerate(self.layers):
            y, new_state[f"main_{i}"] = layer.apply(
                params[f"main_{i}"], state[f"main_{i}"], y, train, rngs[i]
            )
        s = x
        for i, layer in enumerate(self.shortcut):
            s, new_state[f"short_{i}"] = layer.apply(
                params[f"short_{i}"],
                state[f"short_{i}"],
                s,
                train,
                rngs[len(self.layers) + i],
            )
        return get_activation(self.activation)(y + s), new_state

    def get_config(self):
        return {
            "layer": "Residual",
            "layers": [l.get_config() for l in self.layers],
            "shortcut": [l.get_config() for l in self.shortcut],
            "activation": self.activation,
        }

    def sublayers(self):
        return list(self.layers) + list(self.shortcut)


def walk_layers(model_or_layers):
    """Depth-first generator over a model's layers including sublayers —
    THE traversal for mesh-hook attach/detach helpers (ring attention, MoE)
    so they cannot diverge."""
    stack = list(getattr(model_or_layers, "layers", model_or_layers))
    while stack:
        layer = stack.pop()
        yield layer
        stack.extend(layer.sublayers())


class Model:
    """Built model handle: (apply_fn, params, state) + Keras-ish conveniences."""

    def __init__(self, layers, input_shape, params, state):
        self.layers = layers
        self.input_shape = tuple(input_shape)
        self.params = params
        self.state = state

    # -- pure function ------------------------------------------------------

    def apply(self, params, state, x, train=False, rng=None):
        rngs = (
            jax.random.split(rng, len(self.layers))
            if rng is not None
            else [None] * len(self.layers)
        )
        new_state = {}
        for i, layer in enumerate(self.layers):
            # a layer that has no parameters of its own but another's (a
            # tied head: the embedding's) says whose: ``params_of``
            src = getattr(layer, "params_of", i)
            x, new_state[str(i)] = layer.apply(
                params[str(src)], state[str(i)], x, train, rngs[i]
            )
        return x, new_state

    def __call__(self, x, train=False, rng=None):
        y, _ = self.apply(self.params, self.state, x, train=train, rng=rng)
        return y

    def predict(self, x, batch_size=None):
        """Jit-compiled batched inference on the current params."""
        fn = getattr(self, "_predict_fn", None)
        if fn is None:
            fn = jax.jit(lambda p, s, xb: self.apply(p, s, xb, train=False)[0])
            self._predict_fn = fn
        x = jnp.asarray(x)
        if batch_size is None or x.shape[0] <= batch_size:
            return np.asarray(fn(self.params, self.state, x))
        outs = [
            np.asarray(fn(self.params, self.state, x[i : i + batch_size]))
            for i in range(0, x.shape[0], batch_size)
        ]
        return np.concatenate(outs, axis=0)

    # -- weights ------------------------------------------------------------

    def get_weights(self):
        """Flat list of ndarrays in deterministic tree order (PS wire format)."""
        return [np.asarray(w) for w in jax.tree.leaves(self.params)]

    def set_weights(self, weights):
        leaves, treedef = jax.tree.flatten(self.params)
        if len(weights) != len(leaves):
            raise ValueError(
                f"expected {len(leaves)} weight arrays, got {len(weights)}"
            )
        new = [
            jnp.asarray(w, dtype=old.dtype).reshape(old.shape)
            for old, w in zip(leaves, weights)
        ]
        self.params = jax.tree.unflatten(treedef, new)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self.params))

    # -- config -------------------------------------------------------------

    def get_config(self):
        return [l.get_config() for l in self.layers]

    def copy(self) -> "Model":
        return Model(
            self.layers,
            self.input_shape,
            jax.tree.map(lambda a: a, self.params),
            jax.tree.map(lambda a: a, self.state),
        )

    def summary(self) -> str:
        lines = [f"Model(input_shape={self.input_shape})"]
        for i, layer in enumerate(self.layers):
            n = sum(
                int(np.prod(l.shape))
                for l in jax.tree.leaves(self.params[str(i)])
            )
            lines.append(f"  {i}: {layer!r}  params={n}")
        lines.append(f"total params: {self.num_params()}")
        return "\n".join(lines)


class Sequential(Model):
    """Declarative layer stack; call ``build(input_shape)`` to materialize."""

    def __init__(self, layers=None):
        self.layers = list(layers or [])
        self.input_shape = None
        self.params = None
        self.state = None

    def add(self, layer: Layer):
        self.layers.append(layer)

    def build(self, input_shape, seed=0):
        """input_shape excludes the batch dim, e.g. (784,) or (28, 28, 1)."""
        self.input_shape = tuple(int(d) for d in input_shape)
        rng = jax.random.PRNGKey(seed)
        rngs = jax.random.split(rng, max(1, len(self.layers)))
        params, state = {}, {}
        shape = self.input_shape
        for i, layer in enumerate(self.layers):
            p, s, shape = layer.init(rngs[i], shape)
            params[str(i)] = p
            state[str(i)] = s
        self.output_shape = shape
        self.params = params
        self.state = state
        return self

    @classmethod
    def from_config(cls, configs) -> "Sequential":
        return cls([layer_from_config(c) for c in configs])

    def copy(self) -> "Sequential":
        m = Sequential(self.layers)
        m.input_shape = self.input_shape
        if self.params is not None:
            m.output_shape = self.output_shape
            m.params = jax.tree.map(lambda a: a, self.params)
            m.state = jax.tree.map(lambda a: a, self.state)
        return m
