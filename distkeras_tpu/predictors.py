"""Inference over Datasets (reference: distkeras/predictors.py ->
ModelPredictor.predict appends a prediction column via mapPartitions).

Here prediction is a jit-compiled batched forward pass; the ragged final
batch is padded to the batch size so XLA sees one static shape (one
compile). ``data_parallel=True`` is the TPU face of the reference's
all-executors mapPartitions inference: params replicate over a
``Mesh(("data",))`` and each batch shards across the chips — GSPMD runs
the same compiled forward on every device's shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.layers import cache_attention
from distkeras_tpu.ops.quantization import qshape


class Predictor:
    def predict(self, ds: Dataset) -> Dataset:
        raise NotImplementedError


class ModelPredictor(Predictor):
    def __init__(
        self,
        model,
        features_col="features",
        output_col="prediction",
        batch_size=1024,
        data_parallel=False,
        num_workers=None,
        mesh=None,
    ):
        """``data_parallel``: shard each inference batch across the local
        devices (or an explicit ``mesh`` with a "data" axis; ``num_workers``
        limits the device count). ``batch_size`` rounds up to a multiple of
        the mesh size so every shard is equal (the pad rows are sliced off
        the output, same as the ragged-tail pad)."""
        self.model = model
        self.features_col = features_col
        self.output_col = output_col
        self.batch_size = int(batch_size)
        self._in_sh = None
        if data_parallel or mesh is not None:
            from distkeras_tpu.parallel.mesh import (
                batch_sharding,
                local_devices,
                make_mesh,
                replicated_sharding,
            )

            if mesh is None:
                mesh = make_mesh(axis_names=("data",),
                                 devices=local_devices(num_workers))
            else:
                if "data" not in mesh.axis_names:
                    raise ValueError(
                        f"mesh {dict(mesh.shape)} has no 'data' axis"
                    )
                if num_workers is not None:
                    raise ValueError(
                        "num_workers conflicts with an explicit mesh — size "
                        "the mesh itself"
                    )
            n_dev = int(mesh.shape["data"])
            self.batch_size = -(-self.batch_size // n_dev) * n_dev
            self._in_sh = batch_sharding(mesh)
            self._param_sh = replicated_sharding(mesh)
        elif num_workers is not None:
            raise ValueError("num_workers requires data_parallel=True")
        self._fn = jax.jit(
            lambda p, s, x: self.model.apply(p, s, x, train=False)[0]
        )

    def predict(self, ds: Dataset) -> Dataset:
        x = ds[self.features_col]
        n = len(x)
        params, state = self.model.params, self.model.state
        if self._in_sh is not None:
            params = jax.device_put(params, self._param_sh)
            state = jax.device_put(state, self._param_sh)
        outs = []
        for i in range(0, n, self.batch_size):
            chunk = x[i : i + self.batch_size]
            pad = self.batch_size - len(chunk)
            if pad:
                chunk = np.concatenate([chunk, np.zeros((pad, *chunk.shape[1:]), chunk.dtype)])
            if self._in_sh is not None:
                chunk = jax.device_put(chunk, self._in_sh)
            y = np.asarray(self._fn(params, state, chunk))
            outs.append(y[: self.batch_size - pad] if pad else y)
        return ds.with_column(self.output_col, np.concatenate(outs, axis=0))


class SequenceGenerator:
    """Autoregressive decoding for the causal-LM family
    (``zoo.transformer_lm``): the inference-tier counterpart of
    ``ModelPredictor`` for sequence models. No reference counterpart
    (SURVEY §5.7: no sequence models upstream).

    The whole decode is ONE compiled program: a ``lax.scan`` over the
    generated positions, each step running the model's static-shape
    forward on the fixed (B, T) context buffer and writing the next token
    in place — XLA sees one shape, compiles once per (prompt_len, steps).
    Each step recomputes the full prefix (O(T^2 d) per token); at the
    zoo's context lengths that is cheaper than threading a KV cache
    through the layer API, and the compiled scan keeps it on-device with
    zero per-token dispatch.

    ``temperature=0`` decodes greedily; otherwise tokens sample from
    ``softmax(logits / temperature)`` seeded by ``seed`` (same seed, same
    output). ``top_k`` keeps only the k highest logits per step;
    ``top_p`` keeps the smallest nucleus whose probability mass reaches
    p (both static, compiled into the scan; combinable — k first, then
    the nucleus within it).

    Sampling RNG is COUNTER-BASED (``serving.sampling``): each row's
    draw at its e-th generated token keys on ``(seed, e)`` — a pure
    function of the request, independent of batch composition, scan
    bucketing, and neighbours. This makes solo sampled decode the
    identity reference for the serving tier's per-request sampled
    decode (same seed => same tokens), exactly as solo greedy decode
    anchors the serving greedy pins.
    """

    def __init__(self, model, temperature=0.0, seed=0, top_k=None,
                 top_p=None):
        self.model = model
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.top_k = None if top_k is None else int(top_k)
        self.top_p = None if top_p is None else float(top_p)
        self._validate_sampling()
        self._fns = {}  # decode-config key -> compiled scan

    def _validate_sampling(self):
        """Re-checked at every generate(): the sampling config is mutable
        between calls (it keys the compiled-fn cache), so mutation must
        hit the same validation the constructor applies."""
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1; got {self.top_k}")
        if self.top_p is not None and not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1]; got {self.top_p}")
        if (
            (self.top_k is not None or self.top_p is not None)
            and self.temperature == 0
        ):
            raise ValueError(
                "top_k/top_p filter SAMPLING; temperature=0 is greedy "
                "argmax — pass a temperature > 0"
            )

    def _validate_generate_args(self, prompts, steps):
        prompts = np.asarray(prompts)
        if prompts.ndim != 2 or prompts.shape[1] < 1:
            raise ValueError(
                f"prompts must be (B, P) with P >= 1; got {prompts.shape}"
            )
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"steps must be >= 1; got {steps}")
        p = prompts.shape[1]
        seq_len = self.model.input_shape[0]
        if p + steps > seq_len:
            raise ValueError(
                f"prompt ({p}) + steps ({steps}) exceeds the model's "
                f"sequence length ({seq_len})"
            )
        return prompts, steps, seq_len

    def generate(self, prompts, steps, eos_id=None):
        """Continue each prompt by up to ``steps`` tokens.

        ``prompts``: either a (B, P) int array (one shared prompt length)
        or a list/tuple of 1-D int sequences of DIFFERENT lengths (a
        ragged serving batch). max prompt length + steps must fit the
        model's built sequence length.

        ``eos_id``: optional end-of-sequence token id. Generation still
        runs the full compiled scan — XLA wants one static shape, so
        "early exit" is a host-side trim, not a dynamic abort — and each
        returned row is cut after its first generated ``eos_id``
        (inclusive). The wasted tail compute is the price of a single
        compiled program; at serving batch sizes it is cheaper than a
        recompile per exit position.

        Greedy decode of a ragged row is pinned equal to its solo
        rectangular call. SAMPLED rows are deterministic under a fixed
        seed AND batch-composition-independent: each row's e-th
        generated token draws from a counter-based key ``(seed, e)``
        (``serving.sampling``), so a row samples the same tokens next
        to any neighbours, at any bucketing, and alone.

        Returns a (B, P + steps) array for rectangular prompts without
        ``eos_id`` (every row the same length); otherwise a list of B 1-D
        arrays, row i being prompt i followed by its generated tokens.
        """
        self._validate_sampling()
        ragged = isinstance(prompts, (list, tuple)) and len(
            {len(np.atleast_1d(p)) for p in prompts}
        ) > 1
        if not ragged and not isinstance(prompts, np.ndarray):
            prompts = np.asarray(prompts)
        if ragged:
            return self._generate_ragged(prompts, steps, eos_id)
        prompts, steps, seq_len = self._validate_generate_args(prompts, steps)
        b, p = prompts.shape
        ctx = np.zeros((b, seq_len), prompts.dtype)
        ctx[:, :p] = prompts
        # rectangular IS the uniform-lens ragged decode: the keep-prompt/
        # frozen masks are constant-false and the RNG schedule (one split
        # per scanned position) is identical, so one builder serves both
        # (no length bucketing here — a single shared length can't churn
        # compositions, and exact start preserves the pinned rectangular
        # sampling schedule)
        out = self._run_decode(
            ctx, np.full((b,), p, np.int32), p, steps, steps
        )
        out = out[:, : p + steps]
        if eos_id is None:
            return out
        return [self._trim_eos(row, p, int(eos_id)) for row in out]

    def _run_decode(self, ctx, lens, start, n_scan, steps):
        """Compile (cached) and run the decode scan for a batch padded
        into ``ctx``: scanned positions start-1 .. start+n_scan-2. The
        sampling config is baked into the compiled scan, so it keys the
        cache — mutating gen.temperature/top_k/top_p between calls must
        recompile, not silently reuse the old sampling mode."""
        key = (
            start, n_scan, steps,
            self.temperature, self.top_k, self.top_p,
        )
        if key not in self._fns:
            self._fns[key] = self._decode_fn(
                start, n_scan, steps, self.temperature
            )
        return np.asarray(
            self._fns[key](
                self.model.params,
                self.model.state,
                jnp.asarray(ctx),
                jnp.asarray(lens),
                jax.random.PRNGKey(self.seed),
            )
        )

    @staticmethod
    def _trim_eos(row, prompt_len, eos_id):
        """Cut a decoded row after its first GENERATED eos (inclusive);
        eos tokens inside the prompt don't end the sequence."""
        gen = row[prompt_len:]
        hits = np.flatnonzero(gen == eos_id)
        if hits.size:
            return row[: prompt_len + hits[0] + 1]
        return row

    def _generate_ragged(self, prompts, steps, eos_id):
        rows = [np.atleast_1d(np.asarray(p)) for p in prompts]
        if any(r.ndim != 1 or r.shape[0] < 1 for r in rows):
            raise ValueError(
                "ragged prompts must be non-empty 1-D token sequences"
            )
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"steps must be >= 1; got {steps}")
        lens = np.asarray([r.shape[0] for r in rows], np.int32)
        min_len, max_len = int(lens.min()), int(lens.max())
        seq_len = self.model.input_shape[0]
        if max_len + steps > seq_len:
            raise ValueError(
                f"longest prompt ({max_len}) + steps ({steps}) exceeds "
                f"the model's sequence length ({seq_len})"
            )
        dtype = np.result_type(*[r.dtype for r in rows])
        ctx = np.zeros((len(rows), seq_len), dtype)
        for i, r in enumerate(rows):
            ctx[i, : lens[i]] = r
        # Bucket the compiled-program key: exact (min_len, max_len) would
        # compile per length COMPOSITION (O(L^2) programs for a serving
        # workload with naturally varying prompts). The masks are already
        # correct for any scan start <= min(lens), so round the start
        # down to a power of two and the scan length up to one, clamped
        # so the last write lands at seq_len-1 (coverage holds: the
        # validation above guarantees max_len + steps <= seq_len).
        # Greedy AND sampled output are invariant to the bucket: draws
        # key on each row's own (seed, emitted-index) counter.
        start = 1 << (min_len.bit_length() - 1)
        need = max_len - start + steps
        n_scan = min(1 << (need - 1).bit_length(), seq_len - start)
        out = self._run_decode(ctx, lens, start, n_scan, steps)
        res = [out[i, : lens[i] + steps] for i in range(len(rows))]
        if eos_id is not None:
            res = [
                self._trim_eos(row, int(L), int(eos_id))
                for row, L in zip(res, lens)
            ]
        return res

    def _decode_fn(self, min_len, n_scan, steps, temp):
        """Build THE decode scan (rectangular batches are the uniform-
        lens special case). At scanned position pos, rows still inside
        their prompt keep the prompt token (the sampled candidate is
        discarded), rows past their generation window freeze, everyone
        else appends the sampled/greedy token. Each row thus generates
        exactly ``steps`` tokens starting at its own prompt end."""
        apply = self.model.apply

        def decode(params, state, ctx, lens, key):
            del key  # RNG is counter-based: (seed, per-row emitted idx)
            temps, topk, topp, seeds = self._sampling_rows(ctx.shape[0])

            def step(carry, i):
                ctx = carry
                logits, _ = apply(params, state, ctx, train=False)
                pos = min_len - 1 + i
                logit = jax.lax.dynamic_index_in_dim(
                    logits, pos, axis=1, keepdims=False
                )  # (B, V)
                if temp == 0.0:
                    tok = jnp.argmax(logit, axis=-1)
                else:
                    from distkeras_tpu.serving import sampling as _sp

                    epos = jnp.maximum(pos + 1 - lens, 0)  # emitted idx
                    tok = _sp.sample_tokens(
                        logit, temps, topk, topp, seeds, epos
                    )
                ctx, tok = self._masked_write(ctx, lens, steps, pos, tok)
                return ctx, tok

            ctx, _ = jax.lax.scan(step, ctx, jnp.arange(n_scan))
            return ctx

        return jax.jit(decode)

    def _sampling_rows(self, b):
        """Trace-time per-row sampling params (uniform: one config per
        generator) in the vectorized shape ``serving.sampling`` takes —
        THE bridge that makes this solo path and the served per-slot
        path the same computation."""
        return (
            jnp.full((b,), self.temperature, jnp.float32),
            jnp.full((b,), 0 if self.top_k is None else self.top_k,
                     jnp.int32),
            jnp.full((b,), 1.0 if self.top_p is None else self.top_p,
                     jnp.float32),
            jnp.full((b,), self.seed, jnp.int32),
        )

    @staticmethod
    def _masked_write(ctx, lens, steps, pos, tok):
        """Write ``tok`` at column pos+1 under the ragged masks — rows
        still inside their prompt keep the prompt token (the candidate
        is discarded), rows past their generation window freeze (the
        existing pad is written back). The one place the ragged-decode
        invariant lives; both scan bodies call it. Returns (ctx, the
        token actually written)."""
        tok = tok.astype(ctx.dtype)
        cur = jax.lax.dynamic_index_in_dim(
            ctx, pos + 1, axis=1, keepdims=False
        )  # (B,) existing token (prompt or pad)
        in_prompt = (pos + 1) < lens
        frozen = (pos + 1) >= lens + steps
        tok = jnp.where(in_prompt | frozen, cur, tok)
        ctx = jax.lax.dynamic_update_slice_in_dim(
            ctx, tok[:, None], pos + 1, axis=1
        )
        return ctx, tok


class CachedSequenceGenerator(SequenceGenerator):
    """KV-cache decoding for ``zoo.transformer_lm``-shaped models: the
    TPU-native serving path. No reference counterpart (SURVEY §5.7).

    ``SequenceGenerator`` re-runs the full (B, T) forward per token —
    O(T^2 d) a step, fine for training-time spot checks. Decode on real
    hardware is memory-bound, so this subclass keeps each block's K/V in
    a (B, T, H, Dh) cache: the prompt prefills the caches in one
    vectorized pass, then every generated token computes ONE row of
    attention against the cache — O(T d) a step, the whole prefill+scan
    a single compiled program. For DENSE LMs, greedy output is pinned
    equal to the uncached generator's (bit-equal at the default f32
    caches); MoE models are exempt from that pin — see below, the
    uncached path's capacity drops are the part being deliberately not
    reproduced.

    This generator is also THE identity reference for the online
    serving tier: every ``serving.engine.DecodeStepper`` admission
    path — dense or block-PAGED (gather-based attention over a page
    pool), fresh / chunked / prefix-cache-hit / CoW-forked alike — is
    pinned token-identical to this class's solo greedy decode by the
    serving test suite and the committed bench artifacts.

    Supports the LM family's layer shapes: Embedding -> causal
    TransformerBlock xN -> LayerNorm -> Dense (``zoo.transformer_lm``),
    with an optional switch-``MoE`` layer after any block
    (``zoo.moe_transformer_lm``); anything else (attention hooks,
    non-causal blocks) raises rather than decoding incorrectly.

    MoE decoding routes WITHOUT capacity drops (``_moe_nodrop``): the
    capacity budget is a training-throughput device, and the uncached
    full-(B, T) forward even lets context PAD tokens consume it —
    serving wants each real token's true top-1 expert output. The cost
    is computing all E experts and selecting — E x the FFN FLOPs, paid
    per token at decode (tiny) AND over the whole (B, PP) prompt at
    prefill (real; at the zoo family's shapes it is still small, and
    the alternatives lose: gathering per-token expert weights
    materializes (S, D, H) copies — worse than the (E, S, H) hidden
    whenever D > E — and capacity-style dispatch reintroduces the drops
    this path exists to avoid). The win is output that does not depend
    on padding or batch composition.
    """

    def __init__(self, model, temperature=0.0, seed=0, top_k=None,
                 top_p=None, kv_dtype=None):
        """``kv_dtype``: cache dtype; None keeps f32 (greedy output pinned
        bit-equal to the uncached generator). ``jnp.bfloat16`` halves the
        per-token cache-read bytes — the other big HBM stream of the
        serving path next to the int8 weights (ops/quantization.py);
        attention still accumulates in f32 (mixed-dtype einsum promotes)."""
        super().__init__(model, temperature=temperature, seed=seed,
                         top_k=top_k, top_p=top_p)
        self.kv_dtype = jnp.float32 if kv_dtype is None else kv_dtype
        from distkeras_tpu.models.layers import (
            Dense,
            Embedding,
            LayerNorm,
            TransformerBlock,
        )
        from distkeras_tpu.parallel.expert_parallel import MoE

        layers = list(model.layers)
        # which block the model is made of decides which programs the
        # serving engine builds; it is read here, once, and never inside
        # a traced function
        self.block_kind = "kv"
        if self._parse_paged_only(layers):
            return
        shape_err = ValueError(
            "CachedSequenceGenerator supports Embedding -> causal "
            "TransformerBlock xN (each optionally followed by a MoE "
            "layer) -> LayerNorm -> Dense models (zoo.transformer_lm / "
            f"zoo.moe_transformer_lm); got "
            f"{[type(l).__name__ for l in layers]}"
        )
        if not (
            len(layers) >= 4
            and isinstance(layers[0], Embedding)
            and isinstance(layers[-2], LayerNorm)
            and isinstance(layers[-1], Dense)
        ):
            raise shape_err
        # parse the middle into (block, optional MoE) stages, keeping
        # each layer's position — param groups are keyed by layer index
        stages = []  # [(block, block_idx, moe_or_None, moe_idx_or_None)]
        i, mid_end = 1, len(layers) - 2
        while i < mid_end:
            blk = layers[i]
            if not isinstance(blk, TransformerBlock):
                raise shape_err
            moe, moe_idx = None, None
            if i + 1 < mid_end and isinstance(layers[i + 1], MoE):
                moe, moe_idx = layers[i + 1], i + 1
            stages.append((blk, i, moe, moe_idx))
            i += 1 if moe is None else 2
        if not stages:
            raise shape_err
        blocks = [s[0] for s in stages]
        if not all(b.causal for b in blocks):
            raise shape_err
        head_shapes = {(b.mhsa.num_heads, b.mhsa.head_dim) for b in blocks}
        if len(head_shapes) != 1:
            raise ValueError(
                "cached decode derives its cache shape from the first "
                f"block; blocks must share (num_heads, head_dim), got "
                f"{sorted(head_shapes)}"
            )
        for blk in blocks:
            if blk.mhsa.attention_fn is not None:
                raise ValueError(
                    "cached decode computes attention itself; detach the "
                    "attention_fn hook (flash/ring) before decoding"
                )
        self._emb = layers[0]
        self._stages = stages
        self._blocks = blocks
        self._final_ln = layers[-2]
        self._head = layers[-1]

    def _parse_paged_only(self, layers) -> bool:
        """Embedding -> blocks that say one ``kind`` xN -> RMSNorm ->
        Dense (``zoo.mla_moe_lm``, ``zoo.longcat_flash_lm``: kind
        ``"latent"``, whose cache is ``cached_rows`` latent rows a token
        and layer; ``zoo.laguna_lm``: kind ``"gqa"``, grouped-query keys
        and values with a window by layer; ``zoo.granite_hybrid_lm``:
        kind ``"ssm"``, layers that cache a state a sequence and nothing
        a token, which may stand beside ``"gqa"`` layers, and the
        embedding as the head). The block says its kind; its
        class is not asked. The paged ``DecodeStepper`` serves them; the
        solo generators here keep a dense (B, T, H, Dh) cache and refuse
        them (``_decode_prologue``)."""
        from distkeras_tpu.models.layers import Dense, Embedding, TiedHead
        from distkeras_tpu.models.mla_moe import RMSNorm

        mid = layers[1:-2]
        kinds = {getattr(l, "kind", None) for l in mid}
        if not (
            len(layers) >= 4
            and isinstance(layers[0], Embedding)
            and isinstance(layers[-2], RMSNorm)
            and isinstance(layers[-1], (Dense, TiedHead))
            and (kinds in ({"latent"}, {"gqa"}) or (
                "ssm" in kinds and kinds <= {"ssm", "gqa"}))
        ):
            return False
        self.block_kind = "ssm" if "ssm" in kinds else kinds.pop()
        self._emb = layers[0]
        self._stages = [(blk, i + 1, None, None) for i, blk in enumerate(mid)]
        self._blocks = mid
        self._final_ln = layers[-2]
        self._head = layers[-1]
        return True

    def _stage(self, blk, moe, p, pm, x, attend):
        """One (block, optional MoE) stage, where every program walks
        one: the block's arithmetic (``TransformerBlock.forward``) with
        the caller's cache behind ``attend``, then the no-drop branch of
        a switch-``MoE`` layer that follows the block in the model."""
        x = blk.forward(p, x, attend)
        if moe is not None:
            x = x + self._moe_nodrop(pm, x)
        return x

    def _stage_chunk(self, blk, moe, p, pm, x, cache_k, cache_v, pos,
                     qmask):
        """A C-token chunk through one stage against its cache; single-
        token decode is the C=1 case and the speculative verify passes
        C=k+1. x: (B, C, d); caches: (B, T, H, Dh); pos: the chunk's
        first position (K/V write offset); qmask: (C, T) bool, True
        where chunk row c may attend cache position t."""
        kv = []

        def attend(q, k_new, v_new):
            kv.extend(
                jax.lax.dynamic_update_slice(
                    cache, new.astype(cache.dtype), (0, pos, 0, 0)
                )
                for cache, new in ((cache_k, k_new), (cache_v, v_new))
            )
            return cache_attention(q, *kv, qmask)

        x = self._stage(blk, moe, p, pm, x, attend)
        return x, *kv

    def _prefill(self, bp, caches, x):
        """Run ``x`` (B, PP, d) pre-embedded prompt prefix through every
        stage, filling each cache's first PP rows; returns (hidden,
        caches). MoE stages use the same no-drop routing as the decode
        steps, so prefill and per-token outputs agree."""
        from distkeras_tpu.parallel.ring_attention import dense_attention

        pp = x.shape[1]
        new_caches = []
        for (blk, _, moe, _), (p, pm), (ck, cv) in zip(
            self._stages, bp, caches
        ):
            def attend(q, k, v, ck=ck, cv=cv):
                new_caches.append((
                    ck.at[:, :pp].set(k.astype(ck.dtype)),
                    cv.at[:, :pp].set(v.astype(cv.dtype)),
                ))
                return dense_attention(q, k, v, causal=True)

            x = self._stage(blk, moe, p, pm, x, attend)
        return x, new_caches

    def _decode_prologue(self, params, ctx, prompt_len, cache_len=None):
        """Shared trace-time prologue of every cached decode builder:
        unpack the per-layer param groups (one (block, optional-MoE)
        pair per stage, keyed by layer index), build the embed closure,
        allocate the per-stage K/V caches, and prefill positions
        0..prompt_len-2. One copy — beam search, greedy/ragged decode,
        and speculative decode must never drift on cache layout or
        param indexing. ``cache_len`` overrides the cache time axis
        (speculative decode pads it so overrun chunk writes land in
        masked scratch). The embed closure clamps positions to the
        table — a no-op for every kept token; only speculative's
        discarded overrun drafts ever exceed it."""
        if self.block_kind != "kv":
            from distkeras_tpu.models.mla_moe import BlockUnsupportedError

            raise BlockUnsupportedError(
                "the solo cached generators keep a dense (B, T, H, Dh) "
                "K/V cache of one head count; a block that caches latent "
                "rows, grouped keys and values with a window by layer, or "
                "a state a sequence and nothing a token "
                f"(kind {self.block_kind!r}), decodes through the paged "
                "ServingEngine only"
            )
        n_layers = len(self.model.layers)
        if cache_len is None:
            cache_len = self.model.input_shape[0]
        bp = [
            (params[str(bi)], None if mi is None else params[str(mi)])
            for (_, bi, _, mi) in self._stages
        ]
        p_emb = params["0"]
        p_ln = params[str(n_layers - 2)]
        p_head = params[str(n_layers - 1)]
        bsz = ctx.shape[0]
        nh = self._blocks[0].mhsa.num_heads
        hd = qshape(bp[0][0]["mhsa"]["wq"])[1] // nh
        n_pos = (
            p_emb["positions"].shape[0] if "positions" in p_emb else None
        )

        def embed(tok, pos):
            x = p_emb["tokens"][tok]
            if n_pos is not None:
                x = x + p_emb["positions"][jnp.minimum(pos, n_pos - 1)]
            return x

        caches = [
            (
                jnp.zeros((bsz, cache_len, nh, hd), self.kv_dtype),
                jnp.zeros((bsz, cache_len, nh, hd), self.kv_dtype),
            )
            for _ in self._stages
        ]
        if prompt_len > 1:
            pp = prompt_len - 1
            x = p_emb["tokens"][ctx[:, :pp]]
            if "positions" in p_emb:
                x = x + p_emb["positions"][:pp]
            _, caches = self._prefill(bp, caches, x)
        return bp, p_ln, p_head, embed, caches

    @staticmethod
    def _moe_nodrop(p, x):
        """Switch-MoE output for serving: top-1 routing with NO capacity
        drops — every token gets its routed expert's gated output.
        Computes all E experts and selects (E x the FFN FLOPs; at decode
        token counts that is cheap, and the result is independent of
        padding and batch composition, unlike the capacity-dropped
        training path ``parallel.expert_parallel.moe_ffn``, whose
        numbers this matches exactly whenever that path drops nothing).
        Returns the residual branch only (caller adds)."""
        d = x.shape[-1]
        lead = x.shape[:-1]
        tokens = x.reshape(-1, d)
        logits = tokens.astype(jnp.float32) @ p["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        idx = jnp.argmax(probs, axis=-1)  # (S,)
        gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
        h = jnp.einsum("sd,edh->esh", tokens, p["wi"].astype(x.dtype))
        h = jax.nn.gelu(h)
        out_all = jnp.einsum("esh,ehd->esd", h, p["wo"].astype(x.dtype))
        sel = out_all[idx, jnp.arange(tokens.shape[0])]  # (S, d)
        out = sel * gate[:, None].astype(x.dtype)
        return out.reshape(*lead, d)

    def _stages_chunk(self, bp, caches, x, pos, qmask):
        """A (B, C, d) chunk at positions pos..pos+C-1 through every
        stage against the caches (``_stage_chunk`` a stage)."""
        new_caches = []
        for (blk, _, moe, _), (p, pm), (ck, cv) in zip(
            self._stages, bp, caches
        ):
            x, ck, cv = self._stage_chunk(
                blk, moe, p, pm, x, ck, cv, pos, qmask
            )
            new_caches.append((ck, cv))
        return x, new_caches

    def _stages_decode(self, bp, caches, x, pos, t_mask):
        """One token through every stage: the C=1 face of
        ``_stages_chunk``, run by the greedy/ragged scan, beam search,
        and the speculative draft."""
        x, caches = self._stages_chunk(
            bp, caches, x[:, None], pos, t_mask[None, :]
        )
        return x[:, 0], caches

    def _decode_fn(self, min_len, n_scan, steps, temp):
        """THE cached decode builder (rectangular = uniform lens). The
        prefill covers positions 0..min_len-2 — every row's prompt
        reaches at least min_len, so those are real tokens for the whole
        batch; each scanned step then advances one position for
        everyone, with the same keep-prompt / frozen masking as the
        uncached scan (rows re-embed their own prompt tokens until their
        prompt ends, then append exactly ``steps`` generated tokens)."""
        final_ln, head = self._final_ln, self._head
        seq_len = self.model.input_shape[0]

        def decode(params, state, ctx, lens, key):
            del state, key  # RNG is counter-based: (seed, emitted idx)
            bp, p_ln, p_head, embed, caches = self._decode_prologue(
                params, ctx, min_len
            )
            temps, topk, topp, seeds = self._sampling_rows(ctx.shape[0])

            def step(carry, i):
                tok, ctx, caches = carry
                pos = min_len - 1 + i
                x = embed(tok, pos)
                t_mask = jnp.arange(seq_len) <= pos
                x, new_caches = self._stages_decode(
                    bp, caches, x, pos, t_mask
                )
                x, _ = final_ln.apply(p_ln, {}, x)
                logit, _ = head.apply(p_head, {}, x)  # (B, V)
                if temp == 0.0:
                    nxt = jnp.argmax(logit, axis=-1)
                else:
                    from distkeras_tpu.serving import sampling as _sp

                    epos = jnp.maximum(pos + 1 - lens, 0)  # emitted idx
                    nxt = _sp.sample_tokens(
                        logit, temps, topk, topp, seeds, epos
                    )
                ctx, nxt = self._masked_write(ctx, lens, steps, pos, nxt)
                return (nxt, ctx, new_caches), nxt

            tok0 = ctx[:, min_len - 1]
            (_, ctx, _), _ = jax.lax.scan(
                step, (tok0, ctx, caches), jnp.arange(n_scan)
            )
            return ctx

        return jax.jit(decode)


class BeamSearchGenerator(CachedSequenceGenerator):
    """Beam-search decoding for the causal-LM family: keep the
    ``beam_width`` highest-log-probability hypotheses per prompt instead
    of one greedy path. No reference counterpart (SURVEY §5.7).

    The whole search is ONE compiled program, like the other
    generators: beams ride the batch axis of the per-block K/V caches
    ((B*W, T, H, Dh) — ``_block_decode`` is shared verbatim with cached
    greedy decode), and each scanned step expands every live beam over
    the vocabulary, takes the top ``beam_width`` of the B×(W·V) scored
    continuations, and reorders contexts/caches by parent-beam gather.
    The per-step cache gather is the classic beam cost — O(W·T·H·Dh)
    extra HBM traffic per token; serving stacks pay it for better
    sequences, which is exactly the trade this class exposes.

    ``eos_id`` finishes a hypothesis: a finished beam's only extension
    is another ``eos_id`` at zero additional log-probability, so its
    score freezes while open beams keep accumulating. Ranking during
    the search uses raw cumulative log-probability; ``length_penalty``
    (GNMT-style ``((5+L)/6)**alpha``) applies at FINAL selection only,
    favouring longer finished hypotheses at alpha > 0.

    ``beam_width=1`` is pinned equal to greedy cached decode. Scores of
    the returned sequences land in ``self.last_scores`` (raw summed
    log-prob of the winning beam, before the length penalty).
    """

    def __init__(self, model, beam_width=4, length_penalty=0.0,
                 kv_dtype=None):
        super().__init__(model, temperature=0.0, seed=0, kv_dtype=kv_dtype)
        self.beam_width = int(beam_width)
        self.length_penalty = float(length_penalty)
        self._validate_beam()
        self.last_scores = None

    def _validate_beam(self):
        """Re-checked at every generate(), like the parent's sampling
        validation: beam_width/length_penalty are mutable and key the
        compiled-fn cache, so a mutated value must hit the same
        validation the constructor applied."""
        if self.beam_width < 1:
            raise ValueError(
                f"beam_width must be >= 1; got {self.beam_width}"
            )
        vocab = self._emb.vocab_size
        if self.beam_width > vocab:
            raise ValueError(
                f"beam_width ({self.beam_width}) exceeds the vocabulary "
                f"({vocab}) — there are not that many distinct "
                "single-token continuations"
            )
        if self.length_penalty < 0:
            raise ValueError(
                f"length_penalty must be >= 0; got {self.length_penalty}"
            )

    def generate(self, prompts, steps, eos_id=None):
        """(B, P) prompts -> best-scoring continuation per row. Returns
        (B, P + steps) (or a list of eos-trimmed rows when ``eos_id`` is
        given, like the other generators). Ragged batches are not
        supported for beam search — pad/bucket upstream."""
        self._validate_beam()
        if isinstance(prompts, (list, tuple)) and len(
            {len(np.atleast_1d(p)) for p in prompts}
        ) > 1:
            raise ValueError(
                "beam search decodes rectangular batches only; pad or "
                "bucket ragged prompts upstream"
            )
        prompts, steps, seq_len = self._validate_generate_args(
            np.asarray(prompts), steps
        )
        b, p = prompts.shape
        ctx = np.zeros((b, seq_len), prompts.dtype)
        ctx[:, :p] = prompts
        eos = -1 if eos_id is None else int(eos_id)
        key = ("beam", p, steps, eos, self.beam_width, self.length_penalty)
        if key not in self._fns:
            self._fns[key] = self._beam_decode_fn(p, steps, eos)
        out, scores = self._fns[key](
            self.model.params, self.model.state, jnp.asarray(ctx)
        )
        self.last_scores = np.asarray(scores)
        out = np.asarray(out)[:, : p + steps]
        if eos_id is None:
            return out
        return [self._trim_eos(row, p, int(eos_id)) for row in out]

    def _beam_decode_fn(self, prompt_len, steps, eos):
        final_ln, head = self._final_ln, self._head
        seq_len = self.model.input_shape[0]
        W = self.beam_width
        alpha = self.length_penalty

        def decode(params, state, ctx):
            del state
            bsz = ctx.shape[0]
            bp, p_ln, p_head, embed, caches = self._decode_prologue(
                params, ctx, prompt_len
            )
            # tile beams onto the batch axis; beam 0 alone starts live
            # (cum[-inf] elsewhere), so the first expansion picks the W
            # best DISTINCT first tokens instead of W copies of one
            caches = [
                (jnp.repeat(ck, W, axis=0), jnp.repeat(cv, W, axis=0))
                for ck, cv in caches
            ]
            ctxw = jnp.repeat(ctx, W, axis=0).reshape(bsz, W, seq_len)
            cum = jnp.full((bsz, W), -jnp.inf).at[:, 0].set(0.0)
            fin = jnp.zeros((bsz, W), bool)
            glen = jnp.zeros((bsz, W), jnp.int32)
            tok = ctxw[:, :, prompt_len - 1]

            def step(carry, i):
                tok, ctxw, cum, fin, glen, caches = carry
                pos = prompt_len - 1 + i
                x = embed(tok.reshape(-1), pos)  # (B*W, d)
                t_mask = jnp.arange(seq_len) <= pos
                x, new_caches = self._stages_decode(
                    bp, caches, x, pos, t_mask
                )
                x, _ = final_ln.apply(p_ln, {}, x)
                logit, _ = head.apply(p_head, {}, x)  # (B*W, V)
                vocab = logit.shape[-1]
                logp = jax.nn.log_softmax(logit, axis=-1).reshape(
                    bsz, W, vocab
                )
                if eos >= 0:
                    # a finished beam extends only with eos, for free —
                    # its score freezes while open beams keep paying
                    only_eos = jnp.full((vocab,), -jnp.inf).at[eos].set(0.0)
                    logp = jnp.where(
                        fin[:, :, None], only_eos[None, None, :], logp
                    )
                total = (cum[:, :, None] + logp).reshape(bsz, W * vocab)
                cum, flat = jax.lax.top_k(total, W)  # (B, W) each
                parent = flat // vocab
                token = (flat % vocab).astype(tok.dtype)
                # reorder every piece of beam state by parent
                ctxw = jnp.take_along_axis(
                    ctxw, parent[:, :, None], axis=1
                )
                fin = jnp.take_along_axis(fin, parent, axis=1)
                glen = jnp.take_along_axis(glen, parent, axis=1)
                glen = glen + (~fin).astype(jnp.int32)
                if eos >= 0:
                    fin = fin | (token == eos)
                gather = (
                    jnp.arange(bsz)[:, None] * W + parent
                ).reshape(-1)  # (B*W,)
                caches = [
                    (ck[gather], cv[gather]) for ck, cv in new_caches
                ]
                ctxw = jax.lax.dynamic_update_slice_in_dim(
                    ctxw, token[:, :, None].astype(ctxw.dtype),
                    pos + 1, axis=2,
                )
                return (token, ctxw, cum, fin, glen, caches), None

            (tok, ctxw, cum, fin, glen, _), _ = jax.lax.scan(
                step, (tok, ctxw, cum, fin, glen, caches),
                jnp.arange(steps),
            )
            if alpha > 0.0:
                lp = ((5.0 + glen.astype(jnp.float32)) / 6.0) ** alpha
                final_score = cum / lp
            else:
                final_score = cum
            best = jnp.argmax(final_score, axis=1)  # (B,)
            out = jnp.take_along_axis(
                ctxw, best[:, None, None], axis=1
            )[:, 0]
            best_cum = jnp.take_along_axis(cum, best[:, None], axis=1)[:, 0]
            return out, best_cum

        return jax.jit(decode)


class SpeculativeGenerator:
    """Draft-and-verify (speculative) greedy decoding: a small DRAFT
    model proposes ``k`` tokens per round from its own KV caches, the
    TARGET model verifies all k+1 positions in ONE chunked forward, and
    the longest agreeing prefix plus the target's correction token are
    accepted. Output is EXACTLY the target's greedy decode — the draft
    only changes how many target forwards it takes to produce it. No
    reference counterpart (SURVEY §5.7).

    TPU shape: the whole decode is one compiled ``lax.while_loop`` per
    row (dynamic trip count is legal under jit; decode needs no grad),
    so acceptance-dependent progress costs zero recompiles and zero
    host round-trips. Each round is one k-step draft scan plus one
    (k+1)-token target extension — decode is memory-bound, so reading
    the target's weights once per k+1 tokens instead of once per token
    is the win; when the draft disagrees constantly the floor is one
    accepted token per round (plain decode plus draft overhead).

    Rows decode sequentially through one compiled program (per-row
    positions diverge with acceptance; batching them needs per-row
    masks/scatters — a future lift). ``last_rounds`` records verify
    rounds per row; steps/rounds is the measured mean acceptance.

    Numerics: "exactly the target's greedy decode" is exact up to FP
    associativity — the verify chunk contracts its attention einsums in
    a different order than the per-token cached path, a ~1e-6
    difference that could flip argmax only on near-ties (never observed
    on the pinned seeds; trained models have margins). The tests pin
    exact equality on random AND trained models, and the self-draft
    acceptance ceiling exactly.
    """

    def __init__(self, target, draft, k=4, kv_dtype=None):
        self._t = CachedSequenceGenerator(target, kv_dtype=kv_dtype)
        self._d = CachedSequenceGenerator(draft, kv_dtype=kv_dtype)
        self.k = int(k)
        if self.k < 1:
            raise ValueError(f"k must be >= 1; got {k}")
        if self._t._emb.vocab_size != self._d._emb.vocab_size:
            raise ValueError(
                "target and draft must share a vocabulary; got "
                f"{self._t._emb.vocab_size} vs {self._d._emb.vocab_size}"
            )
        if target.input_shape[0] != draft.input_shape[0]:
            raise ValueError(
                "target and draft must be built to the same sequence "
                f"length; got {target.input_shape[0]} vs "
                f"{draft.input_shape[0]}"
            )
        self.target, self.draft = target, draft
        self._fns = {}
        self.last_rounds = None

    def generate(self, prompts, steps, eos_id=None):
        """(B, P) prompts -> the TARGET's greedy continuation, decoded
        speculatively. Same return conventions as the other generators
        ((B, P+steps) array; list of trimmed rows with ``eos_id``)."""
        self.k = int(self.k)
        if self.k < 1:  # re-validated: k is mutable and keys the cache
            raise ValueError(f"k must be >= 1; got {self.k}")
        prompts, steps, seq_len = self._t._validate_generate_args(
            np.asarray(prompts), steps
        )
        b, p = prompts.shape
        key = (p, steps, self.k)
        if key not in self._fns:
            self._fns[key] = self._spec_decode_fn(p, steps)
        outs, rounds = [], []
        for row in prompts:
            ctx = np.zeros((1, seq_len), prompts.dtype)
            ctx[0, :p] = row
            out, n_rounds = self._fns[key](
                self.target.params, self.draft.params, jnp.asarray(ctx)
            )
            outs.append(np.asarray(out)[0, : p + steps])
            rounds.append(int(n_rounds))
        self.last_rounds = np.asarray(rounds)
        out = np.stack(outs)
        if eos_id is None:
            return out
        return [
            SequenceGenerator._trim_eos(r, p, int(eos_id)) for r in out
        ]

    def _extend(self, gen, bp, caches, x, pos, t_pad):
        """Run a (1, C, d) token chunk at positions pos..pos+C-1 through
        ``gen``'s stages against full-length caches: the verify side of
        a round, at C=k+1 with chunk-causal masking."""
        c = x.shape[1]
        qmask = (
            jnp.arange(t_pad)[None, :] <= (pos + jnp.arange(c))[:, None]
        )
        return gen._stages_chunk(bp, caches, x, pos, qmask)

    def _spec_decode_fn(self, prompt_len, steps):
        k = self.k
        seq_len = self.target.input_shape[0]
        # draft chunks and verify writes run up to k positions past the
        # last kept token; pad the working buffers so overrun K/V lands
        # in masked scratch instead of clamping onto real positions
        t_pad = seq_len + k + 1
        tgen, dgen = self._t, self._d

        def decode(t_params, d_params, ctx):
            ctx = jnp.concatenate(
                [ctx, jnp.zeros((1, t_pad - seq_len), ctx.dtype)], axis=1
            )
            t_bp, t_ln, t_head, t_embed, t_caches = tgen._decode_prologue(
                t_params, ctx, prompt_len, cache_len=t_pad
            )
            d_bp, d_ln, d_head, d_embed, d_caches = dgen._decode_prologue(
                d_params, ctx, prompt_len, cache_len=t_pad
            )
            t_mask_grid = jnp.arange(t_pad)

            def draft_chunk(ctx, d_caches, pos):
                """k greedy draft tokens from ctx[pos]; returns (toks
                (k,), caches). The scan runs k+1 steps, discarding the
                last proposal: step j writes the draft's K/V at position
                pos+j, and after a FULLY accepted round the next round
                starts at pos+k+1 — without the extra step, position
                pos+k would stay a zero cache row the next draft chunk
                silently attends over, poisoning every post-full-accept
                proposal (found as a guaranteed rejection after each
                full accept: self-draft measured 5-6 rounds for the
                3-round ceiling)."""

                def step(carry, j):
                    tok, caches = carry
                    x = d_embed(tok, pos + j)
                    t_mask = t_mask_grid <= pos + j
                    x, caches = dgen._stages_decode(
                        d_bp, caches, x, pos + j, t_mask
                    )
                    x, _ = dgen._final_ln.apply(d_ln, {}, x)
                    logit, _ = dgen._head.apply(d_head, {}, x)
                    nxt = jnp.argmax(logit, axis=-1).astype(tok.dtype)
                    return (nxt, caches), nxt[0]

                tok0 = jax.lax.dynamic_index_in_dim(
                    ctx, pos, axis=1, keepdims=False
                )  # (1,)
                (_, caches), toks = jax.lax.scan(
                    step, (tok0, d_caches), jnp.arange(k + 1)
                )
                return toks[:k], caches

            def body(state):
                ctx, t_caches, d_caches, pos, n_gen, rounds = state
                d_toks, d_caches = draft_chunk(ctx, d_caches, pos)
                # target verifies positions pos..pos+k in one chunk
                tok0 = jax.lax.dynamic_index_in_dim(
                    ctx, pos, axis=1, keepdims=False
                )
                chunk = jnp.concatenate([tok0, d_toks])  # (k+1,)
                x = jax.vmap(t_embed, in_axes=(0, 0))(
                    chunk, pos + jnp.arange(k + 1)
                )[None]  # (1, k+1, d)
                x, t_caches = self._extend(
                    tgen, t_bp, t_caches, x, pos, t_pad
                )
                x, _ = tgen._final_ln.apply(t_ln, {}, x)
                logit, _ = tgen._head.apply(t_head, {}, x)  # (1, k+1, V)
                t_arg = jnp.argmax(logit[0], axis=-1).astype(ctx.dtype)
                # accept the agreeing prefix + the target's correction
                agree = d_toks == t_arg[:k]
                n_acc = jnp.argmin(
                    jnp.concatenate([agree, jnp.array([False])])
                )  # first disagreement, k if all agree
                n_new = jnp.minimum(n_acc + 1, steps - n_gen)
                # masked segment write at pos+1 (beyond-budget positions
                # keep their existing — zero-pad — values)
                cur = jax.lax.dynamic_slice(
                    ctx, (0, pos + 1), (1, k + 1)
                )[0]
                seg = jnp.where(jnp.arange(k + 1) < n_new, t_arg, cur)
                ctx = jax.lax.dynamic_update_slice(
                    ctx, seg[None], (0, pos + 1)
                )
                return (
                    ctx, t_caches, d_caches, pos + n_new, n_gen + n_new,
                    rounds + 1,
                )

            def cond(state):
                return state[4] < steps

            state = (
                ctx, t_caches, d_caches,
                jnp.int32(prompt_len - 1), jnp.int32(0), jnp.int32(0),
            )
            ctx, _, _, _, _, rounds = jax.lax.while_loop(cond, body, state)
            return ctx[:, :seq_len], rounds

        return jax.jit(decode)
