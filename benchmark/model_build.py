"""Hands the benchmark's seeded weights to the program's own model.

``zoo.transformer_lm`` is built under ``jax.eval_shape`` (its own random
initialisation, leaf by leaf, is neither computed nor held), its tree is
checked against the reference's layout, and the weights made by
``reference.make_weights`` in one jitted call take its place."""

from __future__ import annotations

import jax


def build_program_model(w: dict, weights):
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
            "benchmark/reference.py documents: the hand-over format moved")
    mlp = model.layers[1]._fc1.units if w["layers"] else w["inner"]
    if mlp != w["inner"]:
        raise RuntimeError(f"program's MLP width {mlp} != n_inner {w['inner']}")
    model.params = weights
    return model
