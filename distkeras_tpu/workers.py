"""Worker runtime: the per-chip training loops.

TPU-native rebuild of the reference's executor-side workers (reference:
distkeras/workers.py -> Worker / SingleTrainerWorker / DOWNPOURWorker /
AEASGDWorker / EAMSGDWorker / ADAGWorker / DynSGDWorker). The Keras
``train_on_batch`` hot loop becomes a jit-compiled ``lax.scan`` over a
*window* of W minibatches (the ``communication_window``): one XLA program
per window keeps the chip busy between host round-trips, which is the
TPU-shaped version of "train W batches between pull/commit".

Async workers split each window into ``begin_window`` (pull + launch device
compute) and ``finish_window`` (fetch result + commit) so that

- thread mode calls them back-to-back per worker thread (true asynchrony,
  one worker per chip), and
- the deterministic simulator interleaves begins/finishes across workers on
  a seeded schedule, reproducing staleness exactly (SURVEY §7.3: async
  semantics need a deterministic test harness).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distkeras_tpu.data.prefetch import Prefetcher
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.metrics import get_metric
from distkeras_tpu.utils.compression import maybe_decode_pull
from distkeras_tpu.utils.tree import host_copy, tree_scale, tree_sub


def _window_unroll(model) -> bool:
    """Whether this model's window scans should fully unroll.

    XLA:CPU executes CONVOLUTION-bearing ``while``-loop bodies ~33x slower
    than the identical ops compiled at top level (measured r5 on the
    north-star CNN window, 1 core: scan 11.1 vs unrolled 373.1 samples/sec;
    partial unroll keeps the loop and stays at ~10 — PERF.md r5). Dense
    models show the OPPOSITE trade: the config-1 MLP measured ~2x FASTER
    under the loop (1,226 vs 603 samples/sec) — so unroll only when a
    Conv2D is actually in the stack. Windows are small by design (default
    8 steps, the communication window), so full unroll costs bounded
    compile time. TPU always keeps the real loop: XLA:TPU loop bodies run
    at full speed, and unrolling would only bloat programs."""
    try:
        if jax.default_backend() != "cpu":
            return False
    except RuntimeError:  # backend not initialized yet: assume accelerator
        return False
    from distkeras_tpu.models.layers import Conv2D

    # _walk_layers (not a local re-walk): attribute-held conv sublayers in
    # composite layers must trigger the unroll too (r5 review finding)
    return any(isinstance(layer, Conv2D) for layer in _walk_layers(model))


# ---------------------------------------------------------------- core cache


def _cast_for_compute(params, x, cdtype):
    """Carry ``cdtype`` into the forward pass. Every layer computes in
    its input's dtype (weights are cast to ``x.dtype`` where they are
    used), so a floating input is cast and the f32 master weights are
    left alone. Integer inputs (token ids) must stay exact — bf16 holds
    8 bits of an id — so there the floating weights are cast instead and
    the embedding's output carries the dtype through the model."""
    if cdtype is None:
        return params, x
    if jnp.issubdtype(x.dtype, jnp.floating):
        return params, x.astype(cdtype)
    return jax.tree.map(
        lambda p: p.astype(cdtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    ), x


def _walk_layers(model):
    """Every layer reachable from ``model`` — delegates to THE canonical
    traversal (``models.sequential.walk_layers``, driven by the
    ``Layer.sublayers()`` contract) rather than re-implementing one: a
    second walker with its own reachability heuristic would silently
    diverge on future composite layers (r5 review finding)."""
    from distkeras_tpu.models.sequential import walk_layers

    return walk_layers(getattr(model, "layers", None) or [])


# Process-local, trace-affecting layer hooks that ``get_config`` cannot
# see: ring/ulysses/flash attachment, the fused-layernorm kernel, and the
# MoE expert mesh. A model carrying ANY of these must bypass the core
# cache — and a cached donor that GROWS one must invalidate its entry —
# or same-config trainers silently trade compiled programs across hook
# states (r5 review findings, two rounds of them).
_RUNTIME_HOOK_ATTRS = ("attention_fn", "norm_fn", "mesh")


def _has_runtime_hooks(model) -> bool:
    return any(
        getattr(layer, attr, None) is not None
        for layer in _walk_layers(model)
        for attr in _RUNTIME_HOOK_ATTRS
    )


def _core_cache_key(model, optimizer_spec, loss, metrics, compute_dtype,
                    remat, accum_steps, aux_loss_weight):
    """Structural fingerprint of everything WorkerCore's compiled programs
    depend on — or None when the core is not safely cacheable (custom optax
    objects, callable losses/metrics, or models with runtime-attached
    attention hooks, which ``get_config`` cannot see)."""
    if optimizer_spec is None or not isinstance(loss, str):
        return None
    if not all(isinstance(m, str) for m in metrics):
        return None
    if getattr(model, "params", None) is None or not hasattr(model, "get_config"):
        return None
    if _has_runtime_hooks(model):
        return None
    import json

    try:
        cfg = json.dumps(model.get_config(), sort_keys=True, default=repr)
    except (TypeError, ValueError):
        return None
    try:
        backend = jax.default_backend()
    except RuntimeError:
        backend = "uninitialized"
    return (
        cfg,
        tuple(getattr(model, "input_shape", None) or ()),
        tuple(optimizer_spec),
        loss,
        tuple(metrics),
        compute_dtype,
        bool(remat),
        int(accum_steps),
        float(aux_loss_weight),
        backend,
    )


_CORE_CACHE: dict = {}
_CORE_CACHE_MAX = 32

# ------------------------------------------------------------------ core step


class WorkerCore:
    """Compiles the shared train/eval step functions for a model+optimizer.

    One core is shared by all workers of a trainer, so XLA compiles each
    program once per device; dispatch follows input placement.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        loss,
        metrics=("accuracy",),
        compute_dtype=None,
        remat=False,
        accum_steps=1,
        aux_loss_weight=0.01,
    ):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = get_loss(loss)
        self.metric_names = list(metrics)
        self.metric_fns = [get_metric(m) for m in metrics]
        self.compute_dtype = compute_dtype
        self.remat = bool(remat)
        # gradient accumulation: each optimizer step runs its batch as
        # accum_steps sequential microbatches (inner lax.scan), averaging
        # gradients — ~k x less activation memory at full-batch numerics
        # (BatchNorm running stats update per microbatch, the standard
        # grad-accum semantics)
        self.accum_steps = int(accum_steps)
        self.aux_loss_weight = float(aux_loss_weight)

        # platform/model-dependent window-scan unroll (see _window_unroll);
        # decided once here, host-side, after the backend is pinned
        unroll = _window_unroll(model)

        def _wscan(f, init, xs):
            return jax.lax.scan(f, init, xs, unroll=unroll or 1)

        model_apply = model.apply
        loss_fn = self.loss_fn
        metric_fns = self.metric_fns
        cdtype = compute_dtype
        aux_w = self.aux_loss_weight

        def train_fwd(params, state, rng, x):
            return model_apply(params, state, x, train=True, rng=rng)

        if remat:
            # rematerialize activations in the backward pass: trades MXU
            # FLOPs for HBM — lets bigger models / windows fit per chip
            train_fwd = jax.checkpoint(train_fwd)

        def compute_loss(params, state, rng, x, y):
            params, x = _cast_for_compute(params, x, cdtype)
            y_pred, new_state = train_fwd(params, state, rng, x)
            y_pred = y_pred.astype(jnp.float32)
            # layers that emit regularizers through state (MoE routing's
            # load-balance loss) contribute aux_w * sum of "aux_loss" leaves;
            # constant-folded away for models without any
            loss = loss_fn(y_pred, y) + aux_w * _collect_aux_losses(new_state)
            return loss, (new_state, y_pred)

        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

        # fused-apply optimizers (ops/pallas_kernels.py) compute new params
        # in one kernel pass; otherwise the standard optax two-step applies
        if hasattr(optimizer, "fused_apply"):
            def apply_opt(params, grads, opt_state):
                return optimizer.fused_apply(params, grads, opt_state)
        else:
            def apply_opt(params, grads, opt_state):
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

        accum = self.accum_steps

        def batch_grads(params, state, sub, bx, by):
            """(loss, state, y_pred, grads) for one optimizer step — the
            whole batch at once, or accumulated over ``accum``
            microbatches (inner scan; grads averaged, so numerics match
            the full-batch step up to summation order)."""
            if accum == 1:
                (loss, (state, y_pred)), grads = grad_fn(
                    params, state, sub, bx, by
                )
                return loss, state, y_pred, grads
            b = bx.shape[0]
            xs_m = bx.reshape(accum, b // accum, *bx.shape[1:])
            ys_m = by.reshape(accum, b // accum, *by.shape[1:])
            subs = jax.random.split(sub, accum)

            def micro(carry, mb):
                state, gacc, lacc = carry
                (loss, (state, y_pred)), grads = grad_fn(
                    params, state, mb["r"], mb["x"], mb["y"]
                )
                gacc = jax.tree.map(jnp.add, gacc, grads)
                return (state, gacc, lacc + loss), y_pred

            g0 = jax.tree.map(jnp.zeros_like, params)
            # a REAL scan on purpose, never _wscan: unrolling here would
            # multiply — window_steps x accum_steps inlined conv graphs in
            # one CPU program (8 x 16 ResNet steps = hours of compile).
            # CPU conv accum pays the while-loop cost; bounded compile
            # beats the throughput win at this nesting (r5 review finding)
            (state, gacc, lsum), y_preds = jax.lax.scan(
                micro, (state, g0, jnp.float32(0.0)),
                {"x": xs_m, "y": ys_m, "r": subs},
            )
            grads = jax.tree.map(lambda g: g / accum, gacc)
            y_pred = y_preds.reshape(b, *y_preds.shape[2:])
            return lsum / accum, state, y_pred, grads

        def train_step(carry, batch):
            params, state, opt_state, rng = carry
            rng, sub = jax.random.split(rng)
            loss, state, y_pred, grads = batch_grads(
                params, state, sub, batch["x"], batch["y"]
            )
            params, opt_state = apply_opt(params, grads, opt_state)
            mets = {"loss": loss}
            for name, fn in zip(self.metric_names, metric_fns):
                mets[name] = fn(y_pred, batch["y"])
            return (params, state, opt_state, rng), mets

        def window(params, state, opt_state, rng, xs, ys):
            """Run a scan over W stacked minibatches; returns per-step metrics."""
            (params, state, opt_state, rng), mets = _wscan(
                train_step, (params, state, opt_state, rng), {"x": xs, "y": ys}
            )
            return params, state, opt_state, rng, mets

        def indexed_window(params, state, opt_state, rng, data_x, data_y, idx):
            """Device-resident window: the full dataset lives in HBM and each
            scan step gathers its minibatch by index (``idx``: (W, B) int32).
            The host ships ~4 bytes/sample of indices per window instead of
            the samples themselves, so steady-state throughput is
            compute-bound, not host-link-bound — the TPU-shaped answer to the
            reference's per-row Python iterator feed (reference:
            distkeras/workers.py -> SingleTrainerWorker minibatch assembly).
            Batch contents match the streamed path exactly for the same
            permutation, so trajectories are bit-identical either way."""

            def step(carry, ix):
                batch = {
                    "x": jnp.take(data_x, ix, axis=0),
                    "y": jnp.take(data_y, ix, axis=0),
                }
                return train_step(carry, batch)

            (params, state, opt_state, rng), mets = _wscan(
                step, (params, state, opt_state, rng), idx
            )
            return params, state, opt_state, rng, mets

        def grad_step(carry, batch):
            params, state, opt_state, rng, acc = carry
            rng, sub = jax.random.split(rng)
            loss, state, y_pred, grads = batch_grads(
                params, state, sub, batch["x"], batch["y"]
            )
            params, opt_state = apply_opt(params, grads, opt_state)
            acc = jax.tree.map(jnp.add, acc, grads)
            mets = {"loss": loss}
            for name, fn in zip(self.metric_names, metric_fns):
                mets[name] = fn(y_pred, batch["y"])
            return (params, state, opt_state, rng, acc), mets

        def grad_window(params, state, opt_state, rng, xs, ys):
            """Like window, but also accumulates raw gradients (ADAG)."""
            acc0 = jax.tree.map(jnp.zeros_like, params)
            (params, state, opt_state, rng, acc), mets = _wscan(
                grad_step, (params, state, opt_state, rng, acc0),
                {"x": xs, "y": ys},
            )
            return params, state, opt_state, rng, acc, mets

        def indexed_grad_window(params, state, opt_state, rng, data_x, data_y, idx):
            """grad_window over the device-resident feed: same contract as
            ``indexed_window`` (HBM-resident pool, (W, B) int32 gather per
            step), same accumulated-gradient output as ``grad_window`` —
            the resident path for the grad-committing async family (ADAG)."""

            def step(carry, ix):
                batch = {
                    "x": jnp.take(data_x, ix, axis=0),
                    "y": jnp.take(data_y, ix, axis=0),
                }
                return grad_step(carry, batch)

            acc0 = jax.tree.map(jnp.zeros_like, params)
            (params, state, opt_state, rng, acc), mets = _wscan(
                step, (params, state, opt_state, rng, acc0), idx
            )
            return params, state, opt_state, rng, acc, mets

        def eval_step(params, state, x, y):
            params, x = _cast_for_compute(params, x, cdtype)
            y_pred, _ = model_apply(params, state, x, train=False)
            y_pred = y_pred.astype(jnp.float32)
            mets = {"loss": loss_fn(y_pred, y)}
            for name, fn in zip(self.metric_names, metric_fns):
                mets[name] = fn(y_pred, y)
            return mets

        self.window = jax.jit(window, donate_argnums=(0, 1, 2))
        self.indexed_window = jax.jit(indexed_window, donate_argnums=(0, 1, 2))
        self.grad_window = jax.jit(grad_window, donate_argnums=(0, 1, 2))
        self.indexed_grad_window = jax.jit(
            indexed_grad_window, donate_argnums=(0, 1, 2)
        )
        self.eval_step = jax.jit(eval_step)
        # unjitted handle for transform composition (the vmapped ensemble
        # jits vmap(window_fn) as ONE program over a stacked member axis)
        self.window_fn = window

    def init_opt_state(self, params):
        return self.optimizer.init(params)

    @classmethod
    def cached(
        cls,
        model,
        optimizer,
        loss,
        *,
        optimizer_spec=None,
        metrics=("accuracy",),
        compute_dtype=None,
        remat=False,
        accum_steps=1,
        aux_loss_weight=0.01,
    ):
        """A WorkerCore whose compiled programs are shared across every
        same-structure construction in the process.

        Constructing a trainer per round (the benchmark matrix's
        epochs-to-target loop; any user retuning in a notebook) used to
        re-trace and re-lower every window program each time — with the r5
        CPU conv-unroll (``_window_unroll``) that cost ~90 s/round on the
        1-core sandbox, dwarfing the actual training. Programs depend only
        on the model's STRUCTURE (apply is pure in params), the optimizer
        spec, loss/metrics names, and the dtype/remat/accum flags — the
        cache key (``_core_cache_key``); anything it cannot fingerprint
        (custom optax objects, callable losses, runtime-attached attention
        hooks) constructs an uncached core exactly as before. The returned
        core carries the CALLER's model object, so ``core.model.params``
        starts (SingleTrainerWorker with ``initial=None``) see the fresh
        weights, never a cache donor's."""
        import os

        key = (
            None
            if os.environ.get("DKT_DISABLE_CORE_CACHE")  # debug kill-switch
            else _core_cache_key(
                model, optimizer_spec, loss, metrics, compute_dtype, remat,
                accum_steps, aux_loss_weight,
            )
        )
        if key is None:
            return cls(
                model, optimizer, loss, metrics=metrics,
                compute_dtype=compute_dtype, remat=remat,
                accum_steps=accum_steps, aux_loss_weight=aux_loss_weight,
            )
        core = _CORE_CACHE.get(key)
        if core is not None:
            # the cached programs traced the donor model's apply; a runtime
            # hook grown SINCE caching would poison future retraces for
            # new shapes — drop the entry instead of trusting it
            if _has_runtime_hooks(core.model):
                del _CORE_CACHE[key]
            else:
                return core._rebound(model)
        # build the programs around a params-stripped structural shell of
        # the model (shared layer objects, no weight arrays): the closures
        # capture the donor's bound ``apply``, so caching a core built on
        # the caller's model would pin that model's full parameter arrays
        # for the cache entry's lifetime (r5 review finding). ``apply``
        # reads structure from ``self.layers`` and takes params explicitly,
        # so the shell traces identically.
        import copy

        shell = copy.copy(model)
        shell.params = None
        shell.state = None
        # model.predict() memoizes a jitted lambda that closes over the
        # DONOR model — carried into the shell it would pin the donor's
        # full parameter arrays, the exact leak the shell prevents
        shell.__dict__.pop("_predict_fn", None)
        core = cls(
            shell, optimizer, loss, metrics=metrics,
            compute_dtype=compute_dtype, remat=remat,
            accum_steps=accum_steps, aux_loss_weight=aux_loss_weight,
        )
        if len(_CORE_CACHE) >= _CORE_CACHE_MAX:  # FIFO bound
            _CORE_CACHE.pop(next(iter(_CORE_CACHE)))
        _CORE_CACHE[key] = core
        return core._rebound(model)

    def _rebound(self, model):
        """Shallow clone sharing the compiled programs, with ``model``
        swapped to the caller's instance (same architecture by key
        construction; ``apply`` is pure, so the traced programs transfer)."""
        import copy

        clone = copy.copy(self)
        clone.model = model
        return clone


def _metrics_to_records(mets) -> list:
    """Device metrics dict of (W,) arrays -> list of per-step float dicts."""
    host = {k: np.asarray(v) for k, v in mets.items()}
    w = len(next(iter(host.values())))
    return [{k: float(v[i]) for k, v in host.items()} for i in range(w)]


def state_leaf_name(path):
    """Name of a model-state pytree leaf from its tree_flatten_with_path
    path: the last path entry's key (dict trees — the model-state layout),
    else its string form. THE definition of which leaves count as
    "aux_loss", shared by the loss collection here and the trainer's
    worker-state aggregation policy."""
    if not path:
        return None
    last = path[-1]
    return last.key if hasattr(last, "key") else str(last)


def _collect_aux_losses(state):
    """Sum of every leaf named "aux_loss" in a model-state pytree — the
    channel layers use to surface differentiable regularizers (MoE's
    switch load-balance loss) to the training loss."""
    total = jnp.zeros((), jnp.float32)
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if state_leaf_name(path) == "aux_loss":
            total = total + jnp.sum(leaf).astype(jnp.float32)
    return total


def stack_window(batches: list, features_col: str, label_col: str):
    """List of W batch dicts -> stacked (W, B, ...) arrays."""
    xs = np.stack([b[features_col] for b in batches])
    ys = np.stack([b[label_col] for b in batches])
    return xs, ys


def iter_windows(dataset, batch_size: int, columns: list, window: int):
    """Group a dataset's batches into window-sized lists, flushing the
    ragged remainder window at the end — THE windowing semantics for every
    windowed trainer (SingleTrainerWorker and Trainer._windowed_epochs both
    route through here so they cannot diverge)."""
    pend = []
    for batch in dataset.batches(batch_size, columns=columns):
        pend.append(batch)
        if len(pend) == window:
            yield pend
            pend = []
    if pend:
        yield pend


def epoch_index_windows(n, batch_size, window, shuffle_seed, epoch):
    """(W, B) int32 index matrices for one epoch of device-resident training.

    THE single encoding of the resident paths' batch-assembly contract: the
    row order is exactly ``Dataset.shuffle(seed + epoch)``'s permutation
    (``np.random.default_rng`` — data/dataset.py), batches cut sequentially,
    remainder rows dropped (``Dataset.batches`` drop_remainder semantics).
    Both SingleTrainerWorker and the sync-DP trainer route through here, so
    the bit-identity guarantee against the streamed path cannot diverge
    between them."""
    perm = (
        np.random.default_rng(shuffle_seed + epoch).permutation(n)
        if shuffle_seed is not None
        else np.arange(n)
    )
    nb = n // batch_size
    idx_all = perm[: nb * batch_size].astype(np.int32).reshape(nb, batch_size)
    for w0 in range(0, nb, window):
        yield idx_all[w0 : w0 + window]


def resident_arrays(dataset, features_col, label_col):
    """Materialize the two training columns for HBM residency, with a clear
    boundary error for datasets that cannot be indexed by column (e.g.
    StreamingDataset, which exists precisely for data that does NOT fit in
    memory — stream those with device_resident=False)."""
    try:
        return (
            np.asarray(dataset[features_col]),
            np.asarray(dataset[label_col]),
        )
    except TypeError as exc:
        raise TypeError(
            "device_resident=True requires an in-memory Dataset whose "
            f"columns can be materialized; got {type(dataset).__name__}. "
            "Use device_resident=False to stream it."
        ) from exc


# --------------------------------------------------------------- sync workers


class SingleTrainerWorker:
    """Sequential minibatch loop on one device (reference:
    distkeras/workers.py -> SingleTrainerWorker.train)."""

    def __init__(self, core: WorkerCore, features_col, label_col, seed=0, device=None):
        self.core = core
        self.features_col = features_col
        self.label_col = label_col
        self.rng = jax.random.PRNGKey(seed)
        self.device = device
        # (samples, dispatch-to-dispatch seconds) per window; at steady state
        # dispatch time tracks device time via queue backpressure
        self.timings = []

    def train(
        self,
        dataset,
        batch_size,
        num_epoch=1,
        window=8,
        shuffle_seed=None,
        initial=None,
        initial_full=None,
        start_epoch=0,
        on_epoch_end=None,
        prefetch=2,
        device_resident=False,
    ):
        """``initial``: optional (params, state) to start from instead of the
        core model's (lets many workers share one compiled core).
        ``initial_full``: optional (params, state, opt_state, rng) — the full
        restore point a checkpoint resume supplies; with ``start_epoch`` this
        makes the continuation bit-identical to an uninterrupted run.
        ``on_epoch_end(epoch, params, state, opt_state, rng)``: checkpoint
        hook, called after each epoch's last window.
        ``prefetch``: windows staged (stack + device_put) by a background
        thread while the device computes the previous window — double
        buffering; 0 restores the synchronous input path. Window order is
        preserved either way, so results are bit-identical.
        ``device_resident``: ship the whole dataset to HBM once and drive
        ``WorkerCore.indexed_window`` with per-epoch shuffled index matrices
        instead of streaming sample windows from the host. Same permutation,
        same batch contents — trajectories stay bit-identical with the
        streamed path — but the per-window host traffic drops from the
        samples themselves to 4 bytes/sample of indices."""
        if initial_full is not None:
            params, state, opt_state, rng = (
                host_copy(initial_full[0]),
                host_copy(initial_full[1]),
                initial_full[2],
                initial_full[3],
            )
        else:
            if initial is not None:
                params, state = host_copy(initial[0]), host_copy(initial[1])
            else:
                params = host_copy(self.core.model.params)
                state = host_copy(self.core.model.state)
            opt_state = self.core.init_opt_state(params)
            rng = self.rng
        if self.device is not None:
            params, state, opt_state = jax.device_put(
                (params, state, opt_state), self.device
            )
        if device_resident:
            return self._train_resident(
                dataset,
                batch_size,
                num_epoch,
                window,
                shuffle_seed,
                params,
                state,
                opt_state,
                rng,
                start_epoch,
                on_epoch_end,
            )

        records = []
        cols = [self.features_col, self.label_col]

        for epoch in range(start_epoch, num_epoch):
            ds = (
                dataset.shuffle(shuffle_seed + epoch)
                if shuffle_seed is not None
                else dataset
            )
            with Prefetcher(
                iter_windows(ds, batch_size, cols, window),
                self._stage_window,
                depth=prefetch,
            ) as staged:
                for xs, ys in staged:
                    params, state, opt_state, rng, records_w = self._run(
                        params, state, opt_state, rng, xs, ys
                    )
                    records.extend(records_w)
            if on_epoch_end is not None:
                on_epoch_end(epoch, params, state, opt_state, rng)
        return params, state, records

    def _train_resident(
        self,
        dataset,
        batch_size,
        num_epoch,
        window,
        shuffle_seed,
        params,
        state,
        opt_state,
        rng,
        start_epoch,
        on_epoch_end,
    ):
        """Device-resident epoch loop: dataset in HBM, indices from the host.

        Batch assembly mirrors the streamed path exactly — per epoch the same
        ``default_rng(seed + epoch).permutation`` order, batches cut
        sequentially, remainder rows dropped (``Dataset.batches``
        drop_remainder semantics) — so the two paths produce bit-identical
        parameter trajectories."""
        n = len(dataset)
        data_x, data_y = resident_arrays(dataset, self.features_col, self.label_col)
        if n // batch_size > 0:  # don't ship a dataset no window will touch
            if self.device is not None:
                data_x, data_y = jax.device_put((data_x, data_y), self.device)
            else:
                data_x, data_y = jax.device_put((data_x, data_y))

        records = []
        for epoch in range(start_epoch, num_epoch):
            for idx in epoch_index_windows(
                n, batch_size, window, shuffle_seed, epoch
            ):
                t0 = time.perf_counter()
                params, state, opt_state, rng, mets = self.core.indexed_window(
                    params, state, opt_state, rng, data_x, data_y, idx
                )
                records_w = _metrics_to_records(mets)
                self.timings.append((idx.size, time.perf_counter() - t0))
                records.extend(records_w)
            if on_epoch_end is not None:
                on_epoch_end(epoch, params, state, opt_state, rng)
        return params, state, records

    def _stage_window(self, batches):
        """Host-side window prep (runs on the prefetch thread): stack the W
        batch dicts and ship the buffers to the device ahead of compute."""
        xs, ys = stack_window(batches, self.features_col, self.label_col)
        if self.device is not None:
            xs, ys = jax.device_put((xs, ys), self.device)
        return xs, ys

    def _run(self, params, state, opt_state, rng, xs, ys):
        t0 = time.perf_counter()
        params, state, opt_state, rng, mets = self.core.window(
            params, state, opt_state, rng, xs, ys
        )
        records = _metrics_to_records(mets)  # forces mets -> window finished
        self.timings.append((xs.shape[0] * xs.shape[1], time.perf_counter() - t0))
        return params, state, opt_state, rng, records


# -------------------------------------------------------------- async workers


class AsyncWorker:
    """Base async worker: owns one partition, one device, one PS connection.

    Lifecycle per window (reference: distkeras/workers.py -> NetworkWorker
    pull/commit cadence):
      begin_window(batches): pull from PS (algorithm-specific), launch the
        compiled window on the device (dispatch is async — the chip computes
        while the host thread yields);
      finish_window(): block on the result, compute the delta, commit.
    """

    uses_grad_window = False

    def __init__(
        self,
        core: WorkerCore,
        ps,
        worker_id: int,
        features_col,
        label_col,
        communication_window: int,
        seed=0,
        device=None,
        compress=None,
    ):
        self.core = core
        self.ps = ps
        self.worker_id = worker_id
        self.features_col = features_col
        self.label_col = label_col
        self.window_size = int(communication_window)
        from distkeras_tpu.utils.compression import parse_compress_spec

        # kinds: None | "int8" | "topk" (frac rides the spec string,
        # e.g. "topk:0.05" — see utils/compression.parse_compress_spec)
        self._compress_kind, self._compress_frac = parse_compress_spec(compress)
        self.compress = compress
        self._q_residual = None  # error-feedback state (utils/compression)
        self._rng0 = jax.random.fold_in(jax.random.PRNGKey(seed), worker_id)
        self.rng = self._rng0
        self.device = device
        self.records = []
        self.timings = []  # (samples, begin->commit seconds) per window
        self._seq = 0  # per-worker commit sequence (exactly-once at the PS)
        self._start_seq = 0  # windows to skip on resume (already absorbed)
        # persistent local slots
        self._params = None
        self._state = None
        self._opt_state = None
        self._pending = None
        # checkpoint/resume of worker-LOCAL state (VERDICT r2 weak #4):
        # when the trainer checkpoints, commits also hand host copies of
        # this worker's replica params (persistent for the elastic
        # algorithms), model state, optimizer moments, rng, and seq to the
        # PS, which stores them in the commit's locked section — the
        # restored system is then a reachable configuration of the async
        # execution, not a center with amnesiac workers. Each handoff costs
        # a device-to-host copy of params+opt_state; snapshot_stride > 1
        # amortizes it (a restored worker then replays at most stride-1
        # windows, which the PS dedup absorbs — "behind" is always safe).
        self.keep_snapshot = False
        self.snapshot_stride = 1
        self._snap = None  # latest committed local state (host copies)
        self._restore_point = None  # snapshot adopted at resume, if any
        # device-resident feed (stage_resident): partition pool in HBM
        self._resident = None
        self._resident_n = 0

    @property
    def ps_failovers(self) -> int:
        """How many times this worker's PS client rotated endpoints (0 for
        in-process / single-endpoint PS connections) — the per-worker face
        of the replicated-PS failover ledger."""
        return int(getattr(self.ps, "failovers", 0))

    def reset_for_retry(self, retry=None):
        """Restart this worker's training after a failure: from its resume
        restore point when it has one, else from scratch.

        From scratch, the commit sequence restarts at 0: the PS has already
        absorbed seqs 0..k, so the re-run's first k+1 commits are
        deduplicated — the retry cannot double-apply work (the reference's
        Spark-retry double-absorb weakness, SURVEY §5.3). After a resume the
        scratch seqs may predate the restored dedup table's window, so the
        retry goes back to the restore point instead. The replay's dedup
        holds across a PS FAILOVER too: the promoted standby's dedup table
        rode the replication stream, so a worker retry that lands on the
        new primary still cannot double-apply pre-crash windows.

        ``retry``: optional ``networking.RetryPolicy`` for the PS redial —
        the shared backoff implementation (the serving client uses the
        same one), for the case where the PS host is itself mid-restart
        when this worker comes back. A remote PS client constructed with
        its own policy already redials under it, and a multi-endpoint
        client's redial rotates through the endpoint list, so the retry
        lands on whichever replica is serving."""
        self.records = []
        self.timings = []
        self._pending = None
        if self._restore_point is not None:
            self._adopt(self._restore_point)
        else:
            self.rng = self._rng0
            self._seq = 0
            self._start_seq = 0
            self._params = None
            self._state = None
            self._opt_state = None
            self._q_residual = None
        if hasattr(self.ps, "reconnect"):
            # a crashed socket stream may be desynced — always redial
            if retry is not None:
                retry.call(self.ps.reconnect)
            else:
                self.ps.reconnect()

    # -- worker-local checkpoint/resume --------------------------------------

    def restore_snapshot(self, snap):
        """Adopt a worker-local checkpoint (see ``keep_snapshot``): replica
        params, model state, optimizer moments, rng position, and commit
        sequence. ``train`` then skips the first ``seq`` windows of the
        partition stream — the ones whose commits the restored PS center
        already contains (same seeded shuffles, so the stream position is
        exact)."""
        self._restore_point = snap
        self._snap = snap  # checkpoints before the first post-resume commit
        self._adopt(snap)  # must still carry this worker's restored state

    def _adopt(self, snap):
        def put(tree):
            tree = host_copy(tree)  # owned copies: never donate the snapshot
            return (
                jax.device_put(tree, self.device)
                if self.device is not None
                else tree
            )

        self._params = put(snap["params"])
        self._state = put(snap["state"])
        self._opt_state = put(snap["opt_state"])
        self.rng = jnp.asarray(np.asarray(snap["rng"]))
        self._seq = int(snap["seq"])
        self._start_seq = int(snap["seq"])
        # residual stays host-side (commit-path state, never donated)
        self._q_residual = host_copy(snap.get("q_residual"))

    # -- algorithm hooks ----------------------------------------------------

    def on_pull(self, center, tag):
        """Set local params from the pulled center. Override per algorithm."""
        raise NotImplementedError

    def make_delta(self, pulled, result):
        """Compute (delta, tag) to commit. Override per algorithm."""
        raise NotImplementedError

    # -- window machinery ---------------------------------------------------

    def _ensure_initialized(self, center):
        if self._state is None:
            self._state = host_copy(self.core.model.state)
            if self.device is not None:
                self._state = jax.device_put(self._state, self.device)
        if self._opt_state is None:
            opt = self.core.init_opt_state(center)
            self._opt_state = (
                jax.device_put(opt, self.device) if self.device is not None else opt
            )

    def begin_window(self, batches):
        # owned host (numpy) copies; worker_id doubles as the PS heartbeat
        center_host, tag = self.ps.pull(worker_id=self.worker_id)
        center_host = maybe_decode_pull(center_host)
        center = (
            jax.device_put(center_host, self.device)
            if self.device is not None
            else center_host
        )
        self._ensure_initialized(center)
        self.on_pull(center, tag)
        xs, ys = stack_window(batches, self.features_col, self.label_col)
        if self.device is not None:
            xs, ys = jax.device_put((xs, ys), self.device)
        fn = self.core.grad_window if self.uses_grad_window else self.core.window
        out = fn(self._params, self._state, self._opt_state, self.rng, xs, ys)
        # keep the host copy for delta computation: the device-side center may
        # be donated by the window call through self._params
        self._pending = {
            "pulled": (center_host, tag),
            "out": out,
            "samples": xs.shape[0] * xs.shape[1],
            "t0": time.perf_counter(),
        }

    def warmup(self, part, batch_size, device_resident=False):
        """Compile this worker's window program before training starts, on
        throwaway state (the trainer's pre-thread warmup: without it every
        worker's first window dispatches into the XLA compile gap, pulls
        the identical initial center, and commits full deltas on top of
        each other — a maximal-staleness burst). Lives on the worker so
        the streamed/indexed program dispatch has exactly one owner
        (mirrors ``begin_window``/``begin_window_indexed``)."""
        batch = next(
            part.batches(
                batch_size, columns=[self.features_col, self.label_col]
            ),
            None,
        )
        if batch is None:  # partition smaller than one batch: nothing to warm
            return
        params = host_copy(self.core.model.params)
        state = host_copy(self.core.model.state)
        opt_state = self.core.init_opt_state(params)
        rng = jax.random.PRNGKey(0)
        if device_resident:
            # the compile keys on the staged pool's shape, so warm against
            # this worker's own pool (stage_resident dedups the re-stage
            # when train() runs)
            self.stage_resident(part)
            idx = np.zeros((self.window_size, batch_size), np.int32)
            fn = (
                self.core.indexed_grad_window
                if self.uses_grad_window
                else self.core.indexed_window
            )
            out = fn(params, state, opt_state, rng, *self._resident, idx)
        else:
            zeros = {k: np.zeros_like(v) for k, v in batch.items()}
            xs, ys = stack_window(
                [zeros] * self.window_size, self.features_col, self.label_col
            )
            fn = (
                self.core.grad_window
                if self.uses_grad_window
                else self.core.window
            )
            out = fn(params, state, opt_state, rng, xs, ys)
        jax.block_until_ready(out)

    def stage_resident(self, dataset):
        """Ship this worker's partition to device memory ONCE; subsequent
        windows stream only the (W, B) int32 index matrices
        (``begin_window_indexed``) — the async face of the device-resident
        input path (same 4-bytes/sample/window host-traffic contract as
        ``SingleTrainerWorker._train_resident``)."""
        if self._resident is not None and self._resident_n == len(dataset):
            return  # already staged (warmup or a retry): the pool is the same
        data_x, data_y = resident_arrays(
            dataset, self.features_col, self.label_col
        )
        self._resident_n = data_x.shape[0]
        if self.device is not None:
            self._resident = jax.device_put((data_x, data_y), self.device)
        else:
            self._resident = jax.device_put((data_x, data_y))

    def iter_index_windows(self, num_epoch, batch_size, shuffle_seed):
        """The resident twin of ``iter_window_batches``: (W, B) index
        matrices, one per commit, across all epochs. Routed through
        ``epoch_index_windows`` so the batch-assembly contract (and with it
        the resume-skip stream alignment) is bit-identical to the streamed
        window stream."""
        for epoch in range(num_epoch):
            yield from epoch_index_windows(
                self._resident_n, batch_size, self.window_size,
                shuffle_seed, epoch,
            )

    def begin_window_indexed(self, idx):
        """``begin_window`` over the device-resident pool: pull + launch,
        shipping only the index matrix for this window."""
        center_host, tag = self.ps.pull(worker_id=self.worker_id)
        center_host = maybe_decode_pull(center_host)
        center = (
            jax.device_put(center_host, self.device)
            if self.device is not None
            else center_host
        )
        self._ensure_initialized(center)
        self.on_pull(center, tag)
        data_x, data_y = self._resident
        samples = int(idx.size)
        if self.device is not None:
            idx = jax.device_put(np.ascontiguousarray(idx), self.device)
        fn = (
            self.core.indexed_grad_window
            if self.uses_grad_window
            else self.core.indexed_window
        )
        out = fn(
            self._params, self._state, self._opt_state, self.rng,
            data_x, data_y, idx,
        )
        self._pending = {
            "pulled": (center_host, tag),
            "out": out,
            "samples": samples,
            "t0": time.perf_counter(),
        }

    def finish_window(self):
        pend = self._pending
        self._pending = None
        if self.uses_grad_window:
            params, state, opt_state, rng, acc, mets = pend["out"]
            result = {"params": params, "grad_acc": acc}
        else:
            params, state, opt_state, rng, mets = pend["out"]
            result = {"params": params}
        self._params, self._state, self._opt_state, self.rng = (
            params,
            state,
            opt_state,
            rng,
        )
        self.records.extend(_metrics_to_records(mets))
        delta, tag = self.make_delta(pend["pulled"], result)
        delta_np = jax.tree.map(np.asarray, delta)
        if self._compress_kind is not None:
            from distkeras_tpu.utils.compression import (
                compress_with_feedback,
                is_compressed,
                is_topk,
                topk_compress_with_feedback,
            )

            # fold last window's compression error in, compress, keep the
            # new residual for the next commit (error feedback). Elastic
            # workers compress inside make_delta instead (the displacement
            # must match what they subtracted locally) and arrive here
            # already compressed. This runs BEFORE the snapshot below so a
            # checkpoint carries THIS commit's residual — a snapshot of the
            # pre-commit residual would make a resume re-apply the previous
            # window's error and drop this one's.
            if not (is_compressed(delta_np) or is_topk(delta_np)):
                if self._compress_kind == "topk":
                    delta_np, self._q_residual = topk_compress_with_feedback(
                        delta_np, self._q_residual, self._compress_frac
                    )
                else:
                    delta_np, self._q_residual = compress_with_feedback(
                        delta_np, self._q_residual
                    )
        local_snap = None
        if self.keep_snapshot and (self._seq + 1) % self.snapshot_stride == 0:
            # host copies of this commit's local state, handed to the PS so
            # it lands in the SAME locked section as the commit: a
            # checkpoint can then never hold a worker state that is ahead
            # of the center it is saved with (behind is safe — the
            # replayed windows dedup at the PS)
            local_snap = self._make_snap(self._seq + 1)
        self.ps.commit(
            delta_np,
            tag,
            commit_id=(self.worker_id, self._seq),
            local_snap=local_snap,
        )
        self._seq += 1
        self.timings.append(
            (pend["samples"], time.perf_counter() - pend["t0"])
        )
        if local_snap is not None:
            self._snap = local_snap

    def _make_snap(self, seq: int) -> dict:
        # host_copy, NOT np.asarray: asarray may alias device buffers on
        # CPU, and these trees are the next window call's DONATED inputs —
        # an aliased long-lived snapshot would be corrupted in place
        snap = {
            "params": host_copy(self._params),
            "state": host_copy(self._state),
            "opt_state": host_copy(self._opt_state),
            "rng": host_copy(self.rng),
            "seq": np.int64(seq),
        }
        if self._q_residual is not None:
            # error-feedback residual rides the snapshot: a resumed
            # compressed run keeps carrying the same quantization error
            snap["q_residual"] = host_copy(self._q_residual)
        return snap

    def final_snapshot(self):
        """Fresh host-copy snapshot of the worker's end-of-run state (the
        trainer's final checkpoint payload; called after threads join, so
        no window is in flight). None if the worker never initialized."""
        if self._params is None or self._opt_state is None:
            return self._snap  # restored-but-never-ran keeps its restore point
        return self._make_snap(self._seq)

    def iter_window_batches(self, dataset, batch_size, num_epoch, shuffle_seed):
        """The worker's window stream: lists of batches, one list per commit
        (full windows plus each epoch's ragged tail), across all epochs.
        Deterministic given the seed — resume skipping relies on that."""
        cols = [self.features_col, self.label_col]
        for epoch in range(num_epoch):
            ds = (
                dataset.shuffle(shuffle_seed + epoch)
                if shuffle_seed is not None
                else dataset
            )
            pend = []
            for batch in ds.batches(batch_size, columns=cols):
                pend.append(batch)
                if len(pend) == self.window_size:
                    yield pend
                    pend = []
            if pend:
                yield pend

    def train(self, dataset, batch_size, num_epoch=1, shuffle_seed=None,
              device_resident=False):
        """Thread-mode entry: run all windows of this worker's partition,
        skipping the first ``_start_seq`` after a resume (their commits are
        already in the restored center).

        ``device_resident``: ship the partition to HBM once and drive the
        indexed window programs with per-epoch index matrices. The window
        stream (same shuffles, same batch contents, same ragged tails) is
        bit-identical to the streamed path, so commit seqs — and with them
        resume skipping and PS dedup — stay aligned across the two modes."""
        if device_resident:
            self.stage_resident(dataset)
            for i, idx in enumerate(
                self.iter_index_windows(num_epoch, batch_size, shuffle_seed)
            ):
                if i < self._start_seq:
                    continue
                self.begin_window_indexed(idx)
                self.finish_window()
            return self.records
        for i, pend in enumerate(
            self.iter_window_batches(dataset, batch_size, num_epoch, shuffle_seed)
        ):
            if i < self._start_seq:
                continue
            self.begin_window(pend)
            self.finish_window()
        return self.records


class DOWNPOURWorker(AsyncWorker):
    """Pull center, run W local steps, commit the weight delta
    (reference: distkeras/workers.py -> DOWNPOURWorker)."""

    def on_pull(self, center, tag):
        self._params = center  # local replica restarts from the center

    def make_delta(self, pulled, result):
        center, tag = pulled
        delta = tree_sub(result["params"], center)
        return delta, tag


class ADAGWorker(AsyncWorker):
    """Accumulated Gradient Normalization (Hermans): run W local steps,
    commit -lr * (sum of gradients) / W (reference: distkeras/workers.py ->
    ADAGWorker; the PS adds the pre-normalized delta)."""

    uses_grad_window = True

    def __init__(self, *args, learning_rate=0.01, **kwargs):
        super().__init__(*args, **kwargs)
        self.learning_rate = float(learning_rate)

    def on_pull(self, center, tag):
        self._params = center

    def make_delta(self, pulled, result):
        scale = -self.learning_rate / float(self.window_size)
        return tree_scale(result["grad_acc"], scale), pulled[1]


class DynSGDWorker(DOWNPOURWorker):
    """DOWNPOUR cadence against the versioned PS: the pull tag (PS update
    counter) rides along with the commit so the server can scale by
    1/(staleness+1) (reference: distkeras/workers.py -> DynSGDWorker)."""


class AEASGDWorker(AsyncWorker):
    """Asynchronous Elastic Averaging SGD (Zhang et al.).

    The local replica persists across windows (it does NOT reset to the
    center). Every window: train W steps, then with elastic force
    e = rho * lr * (x_local - x_center): x_local -= e; commit(e)
    (reference: distkeras/workers.py -> AEASGDWorker; §4.3).
    """

    def __init__(self, *args, rho=5.0, learning_rate=0.01, **kwargs):
        super().__init__(*args, **kwargs)
        self.rho = float(rho)
        self.learning_rate = float(learning_rate)

    def on_pull(self, center, tag):
        if self._params is None:
            self._params = center  # first window: adopt the center

    def make_delta(self, pulled, result):
        center, tag = pulled
        alpha = self.rho * self.learning_rate
        elastic = tree_scale(tree_sub(result["params"], center), alpha)
        if self._compress_kind is not None:
            # the elastic rule applies the displacement on BOTH sides
            # (x_local -= e, center += e); compress BEFORE the local
            # subtraction so both apply the identical reconstructed value —
            # error-feedback-style asymmetry (raw locally, reconstructed at
            # the PS) makes replica and center drift apart and diverges.
            # No residual is kept: the un-shipped remainder stays in
            # x_local and re-enters the next elastic difference, which is
            # its own feedback loop.
            from distkeras_tpu.utils.compression import (
                quantize_tree,
                topk_compress,
            )

            host = jax.tree.map(np.asarray, elastic)
            if self._compress_kind == "topk":
                payload, deq = topk_compress(host, self._compress_frac)
            else:
                payload, deq = quantize_tree(host)
            self._params = tree_sub(result["params"], deq)
            return payload, tag
        self._params = tree_sub(result["params"], elastic)
        return elastic, tag


class EAMSGDWorker(AEASGDWorker):
    """Elastic averaging with momentum: identical elastic rule; the momentum
    lives in the worker's local optimizer (the trainer builds it with
    Nesterov momentum — reference: distkeras/workers.py -> EAMSGDWorker)."""
