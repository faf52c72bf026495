"""Export -> serve -> query: the serving subsystem end to end.

Trains the toy successor-language LM (token t+1 = token t + 1 mod V, so
correct serving is verifiable at a glance), quantizes it to an int8
serving bundle on disk, boots a ``ServingEngine`` FROM THAT BUNDLE (what
a serving host does — the f32 training master never ships), fronts it
with the TCP ``ServingServer``, and then acts as its own traffic: a
burst of concurrent mixed-length ``generate`` calls, a ``predict``
round trip, ``stats``, and a graceful ``stop`` that drains in-flight
work.

Usage:
    python examples/serve_lm.py [--cpu] [--seq 64] [--slots 4]
                                [--speculative [--draft-bundle PATH]]
                                [--fleet N]
                                [--temperature T [--top-p P] [--n N]]

``--temperature`` adds the per-request SAMPLING demo: a seeded sampled
generate (replayed and asserted token-identical — serving sampling is
replay-deterministic), and with ``--n N`` the request decodes N
parallel completions via copy-on-write page forks, printing the n
streams and the pool's shared-page stats.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--speculative", action="store_true",
                    help="serve with speculative decoding: model-free "
                    "prompt-lookup drafting by default, or a trained "
                    "draft LM with --draft-bundle; outputs stay exactly "
                    "the greedy decode")
    ap.add_argument("--draft-bundle", metavar="PATH", default=None,
                    help="with --speculative: train a small draft LM, "
                    "persist it as a quantized serving bundle at PATH, "
                    "and serve draft-and-verify FROM THAT BUNDLE (the "
                    "second-bundle flow a speculative serving host runs)")
    ap.add_argument("--fleet", type=int, metavar="N", default=None,
                    help="serve N engine replicas behind the prefix-"
                    "affinity FleetRouter (all booted from the one "
                    "bundle), then demo a zero-downtime rolling bundle "
                    "upgrade")
    ap.add_argument("--temperature", type=float, default=None,
                    help="demo per-request SAMPLED decode at this "
                    "temperature (seeded: same seed, same tokens — "
                    "replayed and asserted)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus filter for the sampled demo "
                    "(requires --temperature)")
    ap.add_argument("--n", type=int, default=1, metavar="N",
                    help="parallel completions per sampled request, "
                    "decoded via copy-on-write slot forks on the paged "
                    "KV cache (prints shared-page stats)")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.top_p is not None and args.temperature is None:
        ap.error("--top-p filters sampling; pass --temperature too")
    if args.n < 1:
        ap.error("--n must be >= 1")
    if args.n > 1 and args.temperature is None:
        ap.error("--n N parallel completions sample; pass --temperature")
    if (args.temperature is not None) and args.fleet:
        ap.error("--temperature and --fleet are separate demos; pick one")
    if args.draft_bundle and not args.speculative:
        # fail BEFORE training, not after a long run
        ap.error("--draft-bundle feeds the speculative drafter; "
                 "pass --speculative too")
    if args.fleet is not None and args.fleet < 2:
        ap.error("--fleet N needs N >= 2 (one replica is just a "
                 "server; the router exists to spread and fail over)")
    if args.fleet and args.speculative:
        # each knob is its own demo; N speculative engines would just
        # multiply boot time without showing anything new
        ap.error("--fleet and --speculative are separate demos; "
                 "pick one")

    from distkeras_tpu.parallel.backend import setup_backend
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    # the chip, or an error; --cpu asks for the virtual CPU mesh
    enable_compile_cache(setup_backend(cpu=args.cpu, cpu_devices=1))

    from distkeras_tpu import SingleTrainer
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models import zoo
    from distkeras_tpu.ops.quantization import quantize_model
    from distkeras_tpu.serving import ServingClient, ServingEngine, ServingServer
    from distkeras_tpu.utils.serialization import save_serving_bundle

    # -- train the successor LM --------------------------------------------
    rng = np.random.default_rng(0)
    starts = rng.integers(0, args.vocab, 1024)
    xs = ((starts[:, None] + np.arange(args.seq)[None, :]) % args.vocab
          ).astype(np.int32)
    ds = Dataset({"features": xs, "label": xs})
    model = zoo.transformer_lm(
        vocab_size=args.vocab, seq_len=args.seq, d_model=64, num_heads=4,
        depth=2, seed=0,
    )
    trained = SingleTrainer(
        model, "adam", loss="next_token_crossentropy", learning_rate=2e-3,
        batch_size=32, num_epoch=args.epochs, seed=0,
    ).train(ds)

    # -- optionally train + export the DRAFT bundle --------------------------
    spec_kw = {}
    if args.speculative:
        spec_kw = dict(speculative="ngram", draft_k=4)
        if args.draft_bundle:
            # quarter-width single-block draft: cheap enough that its
            # per-round k+1 steps cost well under one target step
            draft = zoo.transformer_lm(
                vocab_size=args.vocab, seq_len=args.seq,
                d_model=16, num_heads=2, depth=1, seed=1,
            )
            draft_t = SingleTrainer(
                draft, "adam", loss="next_token_crossentropy",
                learning_rate=2e-3, batch_size=32,
                num_epoch=args.epochs, seed=0,
            ).train(ds)
            save_serving_bundle(
                args.draft_bundle, quantize_model(draft_t.copy())
            )
            print(f"draft bundle: {os.path.getsize(args.draft_bundle)} "
                  f"bytes at {args.draft_bundle}")
            spec_kw = dict(speculative="draft",
                           draft_bundle=args.draft_bundle, draft_k=4)

    # -- export the serving bundle, boot the engine from DISK ---------------
    with tempfile.TemporaryDirectory() as tmp:
        bundle = os.path.join(tmp, "lm_int8.dkt")
        save_serving_bundle(bundle, quantize_model(trained.copy()))
        print(f"serving bundle: {os.path.getsize(bundle)} bytes")
        if args.fleet:
            serve_fleet(args, bundle)
            return
        paged_kw = {}
        if args.n > 1:
            # n-parallel completions ride copy-on-write page forks:
            # serve the paged KV cache and keep n slots available
            paged_kw = dict(paged=True, page_size=8)
            args.slots = max(args.slots, args.n)
        engine = ServingEngine.from_bundle(
            bundle, num_slots=args.slots, queue_capacity=32, **spec_kw,
            **paged_kw,
        )
        server = ServingServer(engine).start()
        print(f"serving on {server.host}:{server.port} "
              f"({args.slots} slots"
              + (f", speculative={spec_kw['speculative']}"
                 if spec_kw else "") + ")")

        # -- concurrent mixed-length clients --------------------------------
        prompts = [
            np.array([3 % args.vocab], np.int32),
            np.array([x % args.vocab for x in (10, 11, 12)], np.int32),
            np.arange(5, dtype=np.int32) % args.vocab,
            np.array([x % args.vocab for x in (20, 21)], np.int32),
        ]
        steps = min(10, args.seq // 2)
        results = [None] * len(prompts)

        def client(i):
            with ServingClient(server.host, server.port) as c:
                results[i] = c.generate(prompts[i], steps)

        t0 = time.time()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.time() - t0
        for row in results:
            print("served decode:", row.tolist())  # must count upward
        print(f"{len(prompts)} concurrent requests x {steps} tokens "
              f"in {dt:.2f}s")

        # -- per-request sampling demo (--temperature [--top-p] [--n]) ------
        if args.temperature is not None:
            from distkeras_tpu.serving import SamplingParams

            sp = SamplingParams(
                temperature=args.temperature, top_p=args.top_p,
                seed=7, n=args.n,
            )
            with ServingClient(server.host, server.port) as c:
                out = c.generate(prompts[0], steps, sampling=sp)
                outs = out if isinstance(out, list) else [out]
                for j, row in enumerate(outs):
                    print(f"sampled completion {j}: {row.tolist()}")
                replay = c.generate(prompts[0], steps, sampling=sp)
                replays = (
                    replay if isinstance(replay, list) else [replay]
                )
                assert all(
                    np.array_equal(a, b)
                    for a, b in zip(outs, replays)
                ), "same seed must replay identical samples"
                print(f"replayed {len(outs)} completion(s) "
                      f"token-identically (seed {sp.seed})")
                if args.n > 1:
                    pg = c.stats()["paged"]
                    print(f"shared pages: {pg['shared_pages']} shared / "
                          f"{pg['pages_in_use']} in use, "
                          f"{pg['cow_copies']} CoW copies "
                          f"({args.n} completions forked from one "
                          f"prefill)")

        with ServingClient(server.host, server.port) as c:
            logits = c.predict(xs[:2])
            print(f"predict: logits {logits.shape} over the vocab")
            st = c.stats()
            print(f"stats: {st['completed']} completed, mean batch "
                  f"occupancy {st['mean_batch_occupancy']:.2f}, "
                  f"prefill buckets {st['compiled_prefill_buckets']}")
            if args.speculative:
                sp = st["speculative"]
                print(f"speculative[{sp['draft_source']}]: "
                      f"{sp['windows']} verify windows, "
                      f"{sp['mean_tokens_per_window']:.2f} tokens/window, "
                      f"{sp['accepted_draft_tokens']} draft tokens "
                      f"accepted / {sp['rejected_draft_tokens']} "
                      f"rejected, {sp['fallback_steps']} plain-step "
                      f"fallbacks")
            c.stop()  # graceful: drains in-flight work, then closes
        server.shutdown()
        print("drained and stopped")


def serve_fleet(args, bundle):
    """--fleet N: the replicated flow a production serving host runs —
    N replicas booted from ONE bundle behind the prefix-affinity
    router, concurrent shared-header clients (placement visible via
    the ``served_by`` reply stamp), then a zero-downtime rolling
    bundle upgrade and proof the upgraded fleet still serves."""
    from distkeras_tpu.serving import FleetController, ServingClient

    ctl = FleetController(
        bundle, replicas=args.fleet, num_slots=args.slots,
        queue_capacity=32,
    ).start()
    try:
        host, port = ctl.endpoint
        print(f"fleet: {args.fleet} replicas behind router "
              f"{host}:{port} "
              f"({', '.join('%s:%s' % r.endpoint for r in ctl.replicas)})")

        # shared-header traffic: every prompt extends one 16-token
        # header, so prefix affinity must land ALL of them on ONE
        # replica (where the shared KV lives)
        header = (np.arange(16, dtype=np.int32) * 3 + 1) % args.vocab
        prompts = [
            np.concatenate([header,
                            np.asarray(sfx, np.int32) % args.vocab])
            for sfx in ([17], [17, 18], [17, 18, 19], [17, 18, 19, 20])
        ]
        steps = min(10, args.seq // 2)
        results = [None] * len(prompts)
        served = [None] * len(prompts)

        def client(i):
            with ServingClient(host, port) as c:
                results[i] = c.generate(prompts[i], steps)
                served[i] = c.last_served_by

        t0 = time.time()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.time() - t0
        for row in results:
            print("served decode:", row.tolist())  # must count upward
        homes = {s for s in served}
        print(f"{len(prompts)} shared-header requests x {steps} tokens "
              f"in {dt:.2f}s, served by {len(homes)} replica(s): "
              f"{sorted('%s:%s' % h for h in homes)}")

        # rolling upgrade: same bundle stands in for the next training
        # checkpoint — the sequence (boot replacement, health-gate in,
        # drain old, stop old) is identical either way
        ledger = ctl.rollover(bundle)
        print(f"rollover complete: {len(ledger['replaced'])} replicas "
              f"upgraded in {ledger['seconds']}s, zero requests "
              f"dropped")
        with ServingClient(host, port) as c:
            out = c.generate(prompts[0], steps)
            print("served decode (upgraded fleet):", out.tolist())
            h = c.health()
            print(f"fleet health: {h['status']}, "
                  f"{h['active_replicas']} replicas in rotation")
    finally:
        ctl.stop()
    print("drained and stopped")


if __name__ == "__main__":
    main()
