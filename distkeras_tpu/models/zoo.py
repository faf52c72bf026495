"""Model zoo: the architectures behind the five BASELINE configs.

1. ``mnist_mlp``   — SingleTrainer anchor (reference: examples/mnist.py MLP)
2. ``mnist_cnn``   — DOWNPOUR config and the north-star benchmark model
3. ``higgs_mlp``   — AEASGD ATLAS-Higgs tabular classifier
   (reference: examples/workflow.ipynb)
4. ``cifar10_cnn`` — ADAG config
5. ``resnet18``    — DynSGD / ImageNet scale config

All NHWC, float32 params; trainers may run compute in bfloat16.
"""

from __future__ import annotations

from distkeras_tpu.models.layers import (
    Activation,
    BatchNorm,
    Conv2D,
    Dense,
    Dropout,
    Embedding,
    Flatten,
    GlobalAvgPool2D,
    MaxPool2D,
)
from distkeras_tpu.models.sequential import Residual, Sequential


def _scaled(channels: int, width: float) -> int:
    """Channel count under a width multiplier, floored at 8 so narrow smoke
    variants keep every layer trainable (and TPU-lane friendly)."""
    return max(8, int(channels * width))


def mnist_mlp(hidden=500, num_classes=10, seed=0):
    """MLP over flattened 28x28 inputs (input shape (784,))."""
    return Sequential(
        [
            Dense(hidden, activation="relu"),
            Dense(hidden, activation="relu"),
            Dense(num_classes, activation="softmax"),
        ]
    ).build((784,), seed=seed)


def mnist_cnn(num_classes=10, seed=0, width=1.0):
    """Small convnet over (28, 28, 1) images — the north-star bench model.

    ``width``: channel multiplier (conv FLOPs scale ~width^2). The benchmark
    matrix's smoke scale passes <1.0 so a 1-core CPU sandbox can afford the
    epochs-to-target axis; chip captures and the full scale keep 1.0."""
    w = lambda c: _scaled(c, width)
    return Sequential(
        [
            Conv2D(w(32), 3, activation="relu", padding="SAME"),
            Conv2D(w(32), 3, activation="relu", padding="SAME"),
            MaxPool2D(2),
            Conv2D(w(64), 3, activation="relu", padding="SAME"),
            Conv2D(w(64), 3, activation="relu", padding="SAME"),
            MaxPool2D(2),
            Flatten(),
            Dense(w(256), activation="relu"),
            Dropout(0.5),
            Dense(num_classes, activation="softmax"),
        ]
    ).build((28, 28, 1), seed=seed)


def digits_mlp(hidden=64, num_classes=10, seed=0):
    """MLP over the REAL 8x8 handwritten-digit set shipped in-repo
    (``data.loaders.digits`` — flattened 64-pixel inputs). The real-data
    acceptance model: its accuracy numbers are measured against data the
    builder did not design (VERDICT r2 missing #1)."""
    return Sequential(
        [
            Dense(hidden, activation="relu"),
            Dense(hidden, activation="relu"),
            Dense(num_classes, activation="softmax"),
        ]
    ).build((64,), seed=seed)


def tabular_regressor(num_features=10, hidden=64, seed=0):
    """MLP regressor with a linear (B, 1) output head — the regression
    face of the reference's arbitrary-model support (reference:
    distkeras/trainers.py accepts whatever compiled Keras model the user
    hands it, regressors included). Pairs with ``loss="mse"``/"mae" and
    ``RSquaredEvaluator``; the real acceptance data is
    ``loaders.diabetes()``."""
    return Sequential(
        [
            Dense(hidden, activation="relu"),
            Dense(hidden, activation="relu"),
            Dense(1),
        ]
    ).build((num_features,), seed=seed)


def higgs_mlp(num_features=30, hidden=600, num_classes=2, seed=0):
    """ATLAS-Higgs-style tabular classifier (wide MLP over ~30 features)."""
    return Sequential(
        [
            Dense(hidden, activation="relu"),
            Dropout(0.3),
            Dense(hidden, activation="relu"),
            Dropout(0.3),
            Dense(num_classes, activation="softmax"),
        ]
    ).build((num_features,), seed=seed)


def cifar10_cnn(num_classes=10, seed=0, bn_momentum=0.99, width=1.0):
    """VGG-ish convnet over (32, 32, 3).

    ``bn_momentum``: BatchNorm moving-stats momentum. The 0.99 default needs
    hundreds of steps before eval-mode stats track the batch stats; short
    runs (benchmark smoke epochs) should pass ~0.9.
    ``width``: channel multiplier — see :func:`mnist_cnn`."""
    bn = lambda: BatchNorm(momentum=bn_momentum)
    w = lambda c: _scaled(c, width)
    return Sequential(
        [
            Conv2D(w(64), 3, padding="SAME", use_bias=False),
            bn(),
            Activation("relu"),
            Conv2D(w(64), 3, padding="SAME", use_bias=False),
            bn(),
            Activation("relu"),
            MaxPool2D(2),
            Conv2D(w(128), 3, padding="SAME", use_bias=False),
            bn(),
            Activation("relu"),
            Conv2D(w(128), 3, padding="SAME", use_bias=False),
            bn(),
            Activation("relu"),
            MaxPool2D(2),
            Flatten(),
            Dense(w(256), activation="relu"),
            Dropout(0.5),
            Dense(num_classes, activation="softmax"),
        ]
    ).build((32, 32, 3), seed=seed)


def transformer_classifier(
    vocab_size=64,
    seq_len=64,
    d_model=64,
    num_heads=4,
    depth=2,
    num_classes=2,
    seed=0,
    remat=False,
):
    """Sequence classifier: Embedding -> TransformerBlock xN -> mean-pool
    -> softmax head. No reference counterpart (SURVEY §5.7: no attention
    upstream); the rebuild's long-context model family. Pair with
    ``parallel.ring_attention.attach_ring_attention`` to shard the sequence
    axis over a mesh; ``remat=True`` checkpoints each block so activation
    memory stays O(1) in depth (the long-context HBM trade)."""
    from distkeras_tpu.models.layers import (
        Dense,
        Embedding,
        GlobalAvgPool1D,
        LayerNorm,
        TransformerBlock,
    )
    from distkeras_tpu.models.sequential import Sequential

    model = Sequential(
        [
            Embedding(vocab_size, d_model),
            *[TransformerBlock(num_heads, remat=remat) for _ in range(depth)],
            LayerNorm(),
            GlobalAvgPool1D(),
            Dense(num_classes, activation="softmax"),
        ]
    )
    model.build((seq_len,), seed=seed)
    return model


def transformer_lm(
    vocab_size=256,
    seq_len=128,
    d_model=128,
    num_heads=4,
    depth=2,
    seed=0,
    remat=False,
    dropout=0.0,
):
    """Causal language model: Embedding -> causal TransformerBlock xN ->
    LayerNorm -> logits over the vocabulary (no softmax; pair with the
    ``next_token_crossentropy`` loss, which shifts targets by one). No
    reference counterpart (SURVEY §5.7: no sequence models upstream); this
    is the rebuild's autoregressive long-context family — causal blocks
    compose with ``attach_flash_attention`` (masked-block skipping),
    ``attach_blockwise_attention``, and the ring-attention SP trainer the
    same way the classifier does."""
    from distkeras_tpu.models.layers import (
        Dense,
        Embedding,
        LayerNorm,
        TransformerBlock,
    )
    from distkeras_tpu.models.sequential import Sequential

    model = Sequential(
        [
            Embedding(vocab_size, d_model),
            *[
                TransformerBlock(num_heads, causal=True, remat=remat,
                                 dropout=dropout)
                for _ in range(depth)
            ],
            LayerNorm(),
            Dense(vocab_size),
        ]
    )
    model.build((seq_len,), seed=seed)
    return model


def moe_transformer_lm(
    vocab_size=256,
    seq_len=128,
    d_model=128,
    num_heads=4,
    depth=2,
    num_experts=8,
    seed=0,
    remat=False,
):
    """Causal language model with switch-MoE feed-forwards after each
    block — the expert-parallel autoregressive family. Within a row,
    causality is preserved: routing mixes no information across tokens,
    and the capacity cumsum's priority is positional, so a position's
    keep/drop never depends on later tokens. (Capacity is a global
    budget, though — whether a token is dropped can depend on the OTHER
    rows in the batch, so eval logits are batch-composition-dependent,
    as in any capacity-dropped switch MoE.) Pair with
    ``next_token_crossentropy`` and
    ``parallel.expert_parallel.attach_expert_mesh`` to shard the experts.
    No reference counterpart (SURVEY §3.3/§5.7)."""
    from distkeras_tpu.models.layers import (
        Dense,
        Embedding,
        LayerNorm,
        TransformerBlock,
    )
    from distkeras_tpu.models.sequential import Sequential
    from distkeras_tpu.parallel.expert_parallel import MoE

    layers = [Embedding(vocab_size, d_model)]
    for _ in range(depth):
        layers += [
            TransformerBlock(num_heads, causal=True, remat=remat),
            MoE(num_experts),
        ]
    layers += [LayerNorm(), Dense(vocab_size)]
    model = Sequential(layers)
    model.build((seq_len,), seed=seed)
    return model


def moe_transformer_classifier(
    vocab_size=64,
    seq_len=64,
    d_model=64,
    num_heads=4,
    depth=2,
    num_experts=8,
    num_classes=2,
    seed=0,
):
    """Sequence classifier with switch-MoE feed-forwards after each
    transformer block — the expert-parallel model family. Pair with
    ``parallel.expert_parallel.attach_expert_mesh`` to shard the experts
    over a mesh (GSPMD inserts the token<->expert all-to-all); the MoE
    load-balance aux loss reaches the training loss through WorkerCore's
    aux_loss_weight. No reference counterpart (SURVEY §3.3: EP absent
    upstream)."""
    from distkeras_tpu.models.layers import (
        Dense,
        Embedding,
        GlobalAvgPool1D,
        LayerNorm,
        TransformerBlock,
    )
    from distkeras_tpu.models.sequential import Sequential
    from distkeras_tpu.parallel.expert_parallel import MoE

    layers = [Embedding(vocab_size, d_model)]
    for _ in range(depth):
        layers += [TransformerBlock(num_heads), MoE(num_experts)]
    layers += [LayerNorm(), GlobalAvgPool1D(), Dense(num_classes, activation="softmax")]
    model = Sequential(layers)
    model.build((seq_len,), seed=seed)
    return model


def _basic_block(filters, stride=1, downsample=False, bn_momentum=0.99):
    bn = lambda: BatchNorm(momentum=bn_momentum)
    shortcut = (
        [Conv2D(filters, 1, strides=stride, padding="SAME", use_bias=False), bn()]
        if downsample
        else None
    )
    return Residual(
        [
            Conv2D(filters, 3, strides=stride, padding="SAME", use_bias=False),
            bn(),
            Activation("relu"),
            Conv2D(filters, 3, padding="SAME", use_bias=False),
            bn(),
        ],
        shortcut=shortcut,
        activation="relu",
    )


def resnet18(
    num_classes=1000, input_shape=(224, 224, 3), small_stem=False, seed=0,
    bn_momentum=0.99, width=1.0,
):
    """ResNet-18 (NHWC). ``small_stem=True`` swaps the 7x7/s2+maxpool stem for
    a 3x3/s1 stem, the standard CIFAR-scale variant used in smoke tests.
    ``bn_momentum``: see :func:`cifar10_cnn`.
    ``width``: filter multiplier over the whole trunk (same 18-layer
    topology); see :func:`mnist_cnn` for why the benchmark smoke scale
    shrinks it."""
    bn = lambda: BatchNorm(momentum=bn_momentum)
    w = lambda c: _scaled(c, width)
    stem = (
        [Conv2D(w(64), 3, strides=1, padding="SAME", use_bias=False), bn(), Activation("relu")]
        if small_stem
        else [
            Conv2D(w(64), 7, strides=2, padding="SAME", use_bias=False),
            bn(),
            Activation("relu"),
            MaxPool2D(3, strides=2, padding="SAME"),
        ]
    )
    blk = lambda *a, **kw: _basic_block(*a, bn_momentum=bn_momentum, **kw)
    body = [
        blk(w(64)),
        blk(w(64)),
        blk(w(128), stride=2, downsample=True),
        blk(w(128)),
        blk(w(256), stride=2, downsample=True),
        blk(w(256)),
        blk(w(512), stride=2, downsample=True),
        blk(w(512)),
    ]
    head = [GlobalAvgPool2D(), Dense(num_classes, activation="softmax")]
    return Sequential(stem + body + head).build(input_shape, seed=seed)


def mla_moe_lm(
    vocab_size=256,
    seq_len=128,
    hidden_size=64,
    num_heads=4,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    kv_lora_rank=32,
    intermediate_size=128,
    moe_intermediate_size=32,
    n_routed_experts=8,
    num_experts_per_tok=2,
    n_shared_experts=1,
    num_layers=3,
    first_k_dense=1,
    routed_scaling_factor=1.0,
    rope_theta=10000.0,
    rms_norm_eps=1e-6,
    experts_held=None,
    seed=0,
):
    """Causal language model of latent-attention blocks (the
    ``deepseek_v3`` model type, under its published keys): Embedding
    without a position table -> ``first_k_dense`` blocks with a gated MLP
    of ``intermediate_size``, then blocks with ``n_routed_experts`` routed
    experts (``num_experts_per_tok`` a token, sigmoid scores, a selection
    bias) and ``n_shared_experts`` shared ones, each of
    ``moe_intermediate_size`` -> RMSNorm -> an untied head without bias.
    ``experts_held``: the routed experts every expert layer holds (None:
    all); see ``models/mla_moe.py``. Serves through the paged
    ``ServingEngine`` with a latent page in the KV pool."""
    from distkeras_tpu.models.mla_moe import LatentMoEBlock, RMSNorm

    def block(i):
        moe = i >= first_k_dense
        return LatentMoEBlock(
            num_heads, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
            kv_lora_rank, ffn_width=0 if moe else intermediate_size,
            n_experts=n_routed_experts if moe else 0,
            top_k=num_experts_per_tok if moe else 0,
            n_shared=n_shared_experts if moe else 0,
            expert_width=moe_intermediate_size if moe else 0,
            routed_scale=routed_scaling_factor, rope_theta=rope_theta,
            epsilon=rms_norm_eps, experts_held=experts_held if moe else None,
            out_scale=(2 * num_layers) ** -0.5,
        )

    model = Sequential(
        [
            Embedding(vocab_size, hidden_size, with_positions=False),
            *[block(i) for i in range(num_layers)],
            RMSNorm(rms_norm_eps),
            Dense(vocab_size, use_bias=False),
        ]
    )
    model.build((seq_len,), seed=seed)
    return model


def longcat_flash_lm(
    vocab_size=256,
    seq_len=128,
    hidden_size=64,
    num_attention_heads=4,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    kv_lora_rank=32,
    q_lora_rank=48,
    ffn_hidden_size=128,
    expert_ffn_hidden_size=32,
    n_routed_experts=8,
    zero_expert_num=4,
    moe_topk=3,
    num_layers=2,
    routed_scaling_factor=1.0,
    rope_theta=10000.0,
    rms_norm_eps=1e-5,
    mla_scale_q_lora=True,
    mla_scale_kv_lora=True,
    experts_held=None,
    seed=0,
):
    """Causal language model of shortcut-connected expert layers (the
    LongCat-Flash layer, under its published keys): Embedding without a
    position table -> ``num_layers`` x ``ShortcutMoEBlock`` (two latent
    attentions with a low-rank query, two gated MLPs of
    ``ffn_hidden_size``, and ``n_routed_experts`` routed experts of
    ``expert_ffn_hidden_size`` beside ``zero_expert_num`` identity experts,
    ``moe_topk`` a token by softmax scores and a selection bias) -> RMSNorm
    -> an untied head without bias. ``experts_held``: the routed experts
    every layer holds (None: all); see ``models/mla_moe.py``. Serves through
    the paged ``ServingEngine`` with two latent pages a layer in the pool."""
    from distkeras_tpu.models.mla_moe import RMSNorm, ShortcutMoEBlock

    model = Sequential(
        [
            Embedding(vocab_size, hidden_size, with_positions=False),
            *[
                ShortcutMoEBlock(
                    num_attention_heads, qk_nope_head_dim, qk_rope_head_dim,
                    v_head_dim, kv_lora_rank, q_lora_rank, ffn_hidden_size,
                    n_routed_experts, zero_expert_num, moe_topk,
                    expert_ffn_hidden_size,
                    routed_scale=routed_scaling_factor,
                    rope_theta=rope_theta, epsilon=rms_norm_eps,
                    scale_q=mla_scale_q_lora, scale_kv=mla_scale_kv_lora,
                    experts_held=experts_held,
                    out_scale=(2 * num_layers) ** -0.5,
                )
                for _ in range(num_layers)
            ],
            RMSNorm(rms_norm_eps),
            Dense(vocab_size, use_bias=False),
        ]
    )
    model.build((seq_len,), seed=seed)
    return model


def laguna_lm(
    vocab_size=256,
    seq_len=128,
    hidden_size=64,
    num_key_value_heads=2,
    head_dim=16,
    intermediate_size=128,
    moe_intermediate_size=32,
    shared_expert_intermediate_size=32,
    num_experts=8,
    num_experts_per_tok=3,
    layer_types=("full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention",
                 "full_attention"),
    num_attention_heads_per_layer=(4, 6, 6, 6, 4),
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
    gating_types=("per_head",) * 5,
    sliding_window=8,
    rope_parameters=None,
    moe_routed_scaling_factor=2.5,
    norm_topk_prob=True,
    rms_norm_eps=1e-6,
    experts_held=None,
    seed=0,
):
    """Causal language model of grouped-query blocks (the ``laguna`` model
    type, under its published keys): Embedding without a position table ->
    one ``GroupedQueryMoEBlock`` a layer -> RMSNorm -> an untied head
    without bias. Layer ``l`` has ``num_attention_heads_per_layer[l]``
    query heads over ``num_key_value_heads`` K/V heads of ``head_dim``, a
    gate a head (``gating_types[l]``: ``"per_head"``), attends everything
    (``layer_types[l]`` ``"full_attention"``) or the last ``sliding_window``
    positions (``"sliding_attention"``) with that kind's entry of
    ``rope_parameters`` (``{kind: {"rope_theta", "partial_rotary_factor",
    "rope_type": "default" | "yarn", and for YaRN "factor",
    "original_max_position_embeddings", "beta_fast", "beta_slow",
    "attention_factor"}}``; None: plain rotary, theta 1e4, whole heads),
    and a gated MLP of ``intermediate_size`` (``mlp_layer_types[l]``
    ``"dense"``) or ``num_experts`` routed experts of
    ``moe_intermediate_size`` (``num_experts_per_tok`` a token, softmax
    scores normalised over the picks with ``norm_topk_prob``, times
    ``moe_routed_scaling_factor``) beside a shared expert of
    ``shared_expert_intermediate_size``. ``experts_held``: the routed
    experts every expert layer holds (None: all); see
    ``models/gqa_moe.py``. Serves through the paged ``ServingEngine``
    with one page budget for the full layers and a ring a slot for the
    window layers."""
    from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock
    from distkeras_tpu.models.mla_moe import RMSNorm

    n = len(layer_types)
    if not (len(num_attention_heads_per_layer) == len(mlp_layer_types)
            == len(gating_types) == n):
        raise ValueError("the per-layer lists differ in length")
    rope_parameters = rope_parameters or {}

    def rope_of(kind):
        r = rope_parameters.get(kind, {})
        rope_type = r.get("rope_type", "default")
        out = {"theta": float(r.get("rope_theta", 10000.0)),
               "partial": float(r.get("partial_rotary_factor", 1))}
        if rope_type == "yarn":
            out.update(
                factor=float(r["factor"]),
                original=float(r["original_max_position_embeddings"]),
                beta_fast=float(r["beta_fast"]),
                beta_slow=float(r["beta_slow"]),
                attention_factor=float(r.get("attention_factor", 1.0)))
        elif rope_type != "default":
            raise ValueError(f"rope_type {rope_type!r}")
        return out

    def block(i):
        kind, mlp = layer_types[i], mlp_layer_types[i]
        if kind not in ("full_attention", "sliding_attention") or \
                mlp not in ("dense", "sparse") or \
                gating_types[i] not in ("per_head", None):
            raise ValueError(
                f"layer {i}: {kind!r}, {mlp!r}, {gating_types[i]!r}")
        moe = mlp == "sparse"
        return GroupedQueryMoEBlock(
            num_attention_heads_per_layer[i], num_key_value_heads, head_dim,
            rope_of(kind),
            window=sliding_window if kind == "sliding_attention" else None,
            gate=gating_types[i],
            ffn_width=0 if moe else intermediate_size,
            n_experts=num_experts if moe else 0,
            top_k=num_experts_per_tok if moe else 0,
            expert_width=moe_intermediate_size if moe else 0,
            shared_width=shared_expert_intermediate_size if moe else 0,
            routed_scale=moe_routed_scaling_factor,
            norm_topk=norm_topk_prob, epsilon=rms_norm_eps,
            experts_held=experts_held if moe else None,
            out_scale=(2 * n) ** -0.5,
        )

    model = Sequential(
        [
            Embedding(vocab_size, hidden_size, with_positions=False),
            *[block(i) for i in range(n)],
            RMSNorm(rms_norm_eps),
            Dense(vocab_size, use_bias=False),
        ]
    )
    model.build((seq_len,), seed=seed)
    return model


def keye_lm(
    vocab_size=256,
    seq_len=128,
    hidden_size=64,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    moe_intermediate_size=32,
    num_experts=8,
    num_experts_per_tok=3,
    num_hidden_layers=2,
    sa_config=None,
    rope_theta=10000.0,
    norm_topk_prob=True,
    rms_norm_eps=1e-6,
    qk_norm=True,
    experts_held=None,
    seed=0,
):
    """Causal language model of grouped-query blocks whose keys a learned
    indexer selects (the language model of the ``KeyeVL2`` model type, under
    its published keys): Embedding without a position table -> one
    ``GroupedQueryMoEBlock`` a layer -> RMSNorm -> an untied head without
    bias. Every layer alike: ``num_attention_heads`` query heads over
    ``num_key_value_heads`` K/V heads of ``head_dim``, an RMSNorm a head on
    ``q`` and ``k`` (``qk_norm``), plain rotary positions (``rope_theta``;
    a text token's three position components are equal, and the sectioned
    rotation is then the plain one), no gate, no window; the indexer of
    ``sa_config`` (``indexer_num_heads`` heads of ``indexer_head_dim``
    against ONE cached selector key a token, ``indexer_num_kv_heads`` 1;
    the ``topk`` positions of largest score are attended; ``q_chunk_size``
    / ``kv_chunk_size`` name tiles of the computation and change no
    result); then ``num_experts`` routed experts of
    ``moe_intermediate_size`` (``num_experts_per_tok`` a token, softmax
    scores normalised over the picks with ``norm_topk_prob``) and no shared
    expert. ``experts_held``: the routed experts every layer holds (None:
    all). Serves through the paged ``ServingEngine`` with a selector key's
    pool beside the keys' and values' under one page table."""
    from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock
    from distkeras_tpu.models.mla_moe import RMSNorm

    sa = dict(sa_config or {"indexer_num_heads": 2, "indexer_head_dim": 8,
                            "indexer_num_kv_heads": 1, "topk": 8})
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("the indexer caches one selector key a token")
    n = int(num_hidden_layers)
    model = Sequential(
        [
            Embedding(vocab_size, hidden_size, with_positions=False),
            *[GroupedQueryMoEBlock(
                num_attention_heads, num_key_value_heads, head_dim,
                {"theta": float(rope_theta)}, gate=None,
                n_experts=num_experts, top_k=num_experts_per_tok,
                expert_width=moe_intermediate_size, shared_width=0,
                norm_topk=norm_topk_prob, epsilon=rms_norm_eps,
                experts_held=experts_held, out_scale=(2 * n) ** -0.5,
                qk_norm=qk_norm,
                select={"heads": sa["indexer_num_heads"],
                        "head_dim": sa["indexer_head_dim"],
                        "topk": sa["topk"]},
            ) for _ in range(n)],
            RMSNorm(rms_norm_eps),
            Dense(vocab_size, use_bias=False),
        ]
    )
    model.build((seq_len,), seed=seed)
    return model


def granite_hybrid_lm(
    vocab_size=256,
    seq_len=128,
    hidden_size=32,
    num_attention_heads=4,
    num_key_value_heads=2,
    shared_intermediate_size=64,
    layer_types=("mamba", "mamba", "mamba", "attention",
                 "mamba", "mamba", "mamba", "attention"),
    mamba_n_heads=8,
    mamba_d_head=8,
    mamba_d_state=16,
    mamba_n_groups=1,
    mamba_d_conv=4,
    mamba_expand=2,
    mamba_chunk_size=8,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=None,
    logits_scaling=8.0,
    rms_norm_eps=1e-5,
    seed=0,
):
    """Causal language model of Mamba-2 layers beside grouped-query
    attention layers (the ``granitemoehybrid`` model type with no routed
    experts, under its published keys): Embedding without a position table,
    times ``embedding_multiplier`` -> one block a layer, ``layer_types[l]``
    ``"mamba"`` (``Mamba2Block``: ``mamba_n_heads`` heads of
    ``mamba_d_head`` = ``mamba_expand x hidden_size`` values, a state of
    ``mamba_d_state``, ``mamba_n_groups`` 1, a convolution of
    ``mamba_d_conv``, blocks of ``mamba_chunk_size``) or ``"attention"``
    (``GroupedQueryMoEBlock``: ``num_attention_heads`` query heads over
    ``num_key_value_heads`` K/V heads of ``hidden_size /
    num_attention_heads``, NO rotation and no position table, scores times
    ``attention_multiplier``; None: ``1 / sqrt(head size)``), each followed
    by a gated MLP of ``shared_intermediate_size``, every branch times
    ``residual_multiplier`` -> RMSNorm -> the embedding as the head
    (``TiedHead``), logits over ``logits_scaling``. A Mamba layer's state is
    held in float32. Serves through the paged
    ``ServingEngine``: a state and a convolution tail a slot for the Mamba
    layers beside the attention layers' pages."""
    from distkeras_tpu.models.gqa_moe import GroupedQueryMoEBlock
    from distkeras_tpu.models.layers import TiedHead
    from distkeras_tpu.models.mamba2 import Mamba2Block
    from distkeras_tpu.models.mla_moe import RMSNorm

    if hidden_size % num_attention_heads or (
            mamba_n_heads * mamba_d_head != mamba_expand * hidden_size):
        raise ValueError(
            f"hidden_size {hidden_size}: {num_attention_heads} attention "
            f"heads, {mamba_n_heads} Mamba heads of {mamba_d_head} at "
            f"expand {mamba_expand}")
    # the model's own residual_multiplier is its scaling of a branch by
    # depth: the output projections are not damped a second time
    out_scale = 1.0

    def block(kind):
        if kind == "mamba":
            return Mamba2Block(
                mamba_n_heads, mamba_d_head, mamba_d_state,
                shared_intermediate_size, n_groups=mamba_n_groups,
                conv_width=mamba_d_conv, chunk=mamba_chunk_size,
                epsilon=rms_norm_eps, residual_scale=residual_multiplier,
                out_scale=out_scale)
        if kind != "attention":
            raise ValueError(f"layer type {kind!r}")
        return GroupedQueryMoEBlock(
            num_attention_heads, num_key_value_heads,
            hidden_size // num_attention_heads, None, gate=None,
            ffn_width=shared_intermediate_size, epsilon=rms_norm_eps,
            out_scale=out_scale, softmax_scale=attention_multiplier,
            residual_scale=residual_multiplier)

    model = Sequential(
        [
            Embedding(vocab_size, hidden_size, with_positions=False,
                      multiplier=embedding_multiplier),
            *[block(kind) for kind in layer_types],
            RMSNorm(rms_norm_eps),
            TiedHead(vocab_size, logits_scaling),
        ]
    )
    model.build((seq_len,), seed=seed)
    return model


ZOO = {
    "mnist_mlp": mnist_mlp,
    "mnist_cnn": mnist_cnn,
    "higgs_mlp": higgs_mlp,
    "cifar10_cnn": cifar10_cnn,
    "resnet18": resnet18,
    "transformer_classifier": transformer_classifier,
    "moe_transformer_classifier": moe_transformer_classifier,
}
