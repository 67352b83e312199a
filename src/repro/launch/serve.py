"""Serving launchers.

Two modes:

* ``lm``      — continuous-batching LM engine over the paged KV cache:
                  python -m repro.launch.serve --mode lm --arch glm4-9b
* ``extract`` — polytope extraction service under a Zipfian request mix
  (the production pattern: a few hot crops dominate traffic), serving
  plans from the LRU plan cache (DESIGN.md §4) and reading the float32
  payload on the device:
                  python -m repro.launch.serve --mode extract --requests 512

Both modes keep JAX's compile cache in ``$JAX_COMPILATION_CACHE_DIR``,
or in ``<repo>/.jax_cache`` when that is unset.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.launch import use_compile_cache


def run_lm(args) -> None:
    import importlib

    import jax

    from repro.configs import _MODULES
    from repro.models.transformer import init_params
    from repro.serve.engine import EngineConfig, Request, ServeEngine

    mod = importlib.import_module(f"repro.configs.{_MODULES[args.arch]}")
    if not hasattr(mod, "_smoke"):
        raise SystemExit(f"{args.arch} has no LM smoke config")
    cfg = mod._smoke()
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, EngineConfig(
        max_batch=4, max_seq=128, page_size=16, n_pages=256))

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        engine.submit(Request(
            prompt=rng.integers(0, cfg.vocab, rng.integers(4, 24)
                                ).astype(np.int32),
            max_new_tokens=args.max_new_tokens))
    done = engine.run()
    dt = time.time() - t0
    n_tok = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests / {n_tok} tokens "
          f"in {dt:.1f}s ({n_tok / dt:.1f} tok/s)")
    print(f"KV pool utilization at end: {engine.pager.utilization:.0%}")


class ClientError(RuntimeError):
    """A load-generating client thread raised; the run is not valid."""


def load_payload(wc, seed: int):
    """The cube's float32 payload made from ``seed``: returns the host
    copy and the same array placed once on JAX's default device."""
    import jax

    host = wc.field_data(seed)
    return host, jax.device_put(host)


def run_extract(args, payload=None):
    """Closed-loop Zipfian load against the sharded service: ``--threads``
    clients submit through one :class:`AdmissionQueue` (so duplicate hot
    crops coalesce across callers inside each arrival window), and a
    summary of the run is printed.

    The payload is read on the device: ``payload`` is the device array
    of ``load_payload`` for the same cube, or ``None`` to build it here.
    Returns ``(row, answers)``: the summary's figures and every answered
    ``ServiceResult`` in submission order per client.  Raises
    :class:`ClientError` if any client thread raised.
    """
    import threading

    from repro.dataplane.weather import WeatherCube, request_population
    from repro.serve.sharded import AdmissionQueue, ShardedExtractionService

    if args.zipf_s <= 1.0:
        raise SystemExit("--zipf-s must be > 1 (Zipf exponent)")
    wc = WeatherCube(n=args.grid_n, n_times=args.n_times,
                     n_levels=args.n_levels, dtype=np.dtype(np.float32))
    if payload is None:
        _, payload = load_payload(wc, args.seed)
    svc = ShardedExtractionService(
        wc.cube, shards=args.shards,
        capacity_per_shard=args.cache_capacity)
    population = request_population(wc)

    rng = np.random.default_rng(args.seed)
    ranks = np.minimum(rng.zipf(args.zipf_s, size=args.requests) - 1,
                       len(population) - 1)
    per_thread = np.array_split(ranks, max(args.threads, 1))
    latencies = [np.empty(0)] * len(per_thread)
    answers: list[list] = [[] for _ in per_thread]
    errors: list[BaseException | None] = [None] * len(per_thread)
    barrier = threading.Barrier(len(per_thread) + 1)

    def client(tid: int, my_ranks: np.ndarray, queue: AdmissionQueue):
        lat = np.empty(len(my_ranks))
        barrier.wait()
        try:
            for i, r in enumerate(my_ranks):
                t0 = time.perf_counter()
                answers[tid].append(
                    queue.extract(population[int(r)], timeout=60))
                lat[i] = time.perf_counter() - t0
        except Exception as e:   # reported after the join, never lost
            errors[tid] = e
        latencies[tid] = lat

    with AdmissionQueue(svc, flat_data=payload,
                        window_s=args.window_ms / 1e3) as queue:
        threads = [threading.Thread(target=client, args=(i, tr, queue))
                   for i, tr in enumerate(per_thread)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        adm = queue.snapshot()

    failed = [e for e in errors if e is not None]
    if failed:
        raise ClientError(f"{len(failed)} of {len(per_thread)} client "
                          f"threads failed: {failed[0]!r}") from failed[0]

    lat_ms = np.concatenate(latencies) * 1e3
    if not len(lat_ms):  # --requests 0: no latency to take a percentile of
        lat_ms = np.zeros(1)
    s = svc.stats
    row = {
        "requests": int(len(ranks)),
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "req_per_s": float(len(ranks) / dt) if dt else 0.0,
    }
    print(f"served {len(ranks)} requests from {len(per_thread)} threads "
          f"in {dt:.2f}s ({row['req_per_s']:.0f} req/s)")
    print(f"latency p50 {row['p50_ms']:.2f}ms / p99 {row['p99_ms']:.2f}ms")
    print(f"plan cache: {s.hits} hits / {s.misses} misses "
          f"(+{s.batch_dedup} batch-dedup) = {s.hit_rate:.0%} hit rate, "
          f"{s.evictions} evictions across {args.shards} shards")
    print(f"admission: {adm.windows} windows (max {adm.window_max}), "
          f"{adm.coalesced} coalesced, "
          f"factor {adm.coalescing_factor:.2f}x")
    print(f"planning {s.plan_time_s:.2f}s, shared gather "
          f"{s.gather_time_s:.2f}s, read sharing {s.sharing_factor:.2f}x")
    per_win = 1e3 / max(adm.windows, 1)
    print(f"stages: admission wait "
          f"{1e3 * adm.wait_s / max(adm.submitted, 1):.2f}ms/req; per "
          f"window lookup {s.lookup_time_s * per_win:.2f}ms, union "
          f"{s.union_time_s * per_win:.2f}ms, launch "
          f"{s.launch_time_s * per_win:.2f}ms, copy "
          f"{s.copy_time_s * per_win:.2f}ms, slice "
          f"{s.slice_time_s * per_win:.2f}ms; launch padding "
          f"{s.gather_padded_elems} elements, union runs {s.union_runs}")
    return row, [a for per in answers for a in per]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["lm", "extract"], default="lm")
    ap.add_argument("--requests", type=int, default=8)
    # lm mode
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    # extract mode
    ap.add_argument("--grid-n", type=int, default=32)
    ap.add_argument("--n-times", type=int, default=4)
    ap.add_argument("--n-levels", type=int, default=4)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--cache-capacity", type=int, default=256)
    ap.add_argument("--zipf-s", type=float, default=1.3)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    if args.mode == "extract":
        try:
            run_extract(args)
        except ClientError as e:
            print(f"serve: {e}", file=sys.stderr)
            raise SystemExit(1) from e
    else:
        from repro.configs import ARCH_IDS

        if args.arch not in ARCH_IDS:
            raise SystemExit(f"unknown arch {args.arch}")
        run_lm(args)


if __name__ == "__main__":
    main()
