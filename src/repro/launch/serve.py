"""Serving launcher: prefill + batched autoregressive decode, or a
continuous-batching co-simulation service over the accelerator ILAs.

LLM decode:

    python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
        [--batch 4] [--prompt 16] [--gen 16]

Co-sim serving (ROADMAP: serving front end over the simulated fleet):

    python -m repro.launch.serve --cosim resmlp --devices-per-target 2 \
        [--requests 16] [--batch 2] [--engine pipelined] [--mesh auto] \
        [--warmup 1] [--concurrency 4] [--queue-depth 16] \
        [--arrival poisson:8] [--no-coalesce] [--no-overlap]

compiles the named application once (cost-driven flexible matching) and
serves it through :class:`repro.core.serving.CosimServer`: a bounded
request queue + single dispatch thread where request k+1's host packing
overlaps request k's simulation tail (``submit_many``/``prepack_many``),
queued same-app requests coalesce into one vmapped dispatch, and
admission control rejects work beyond ``--queue-depth``. Warmup requests
and measured requests both run on ``--engine`` (default ``pipelined``, or
``REPRO_ENGINE``).

``--concurrency N`` bounds the load generator's outstanding requests;
``--arrival poisson:RATE`` draws exponential inter-arrival gaps at RATE
requests/second (default ``asap``: back-to-back). The run reports
sustained QPS, p50/p95/p99 request latency, rejections, then the
per-device utilization, pipeline-stage and cache-health tables. All
engines serve bit-identical results for a given ``--seed`` (request
operands derive from ``(seed, request_id)``, independent of queue or
coalescing order). See ``docs/serving.md``.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np


def _force(*trees):
    """Block until every array in the pytrees is computed — JAX dispatch is
    async, so timing without this measures enqueue, not compute."""
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()


def _parse_arrival(spec: str):
    """"asap" -> None (back-to-back); "poisson:RATE" -> RATE (req/s)."""
    if spec == "asap":
        return None
    if spec.startswith("poisson:"):
        rate = float(spec.split(":", 1)[1])
        if rate <= 0:
            raise SystemExit(f"--arrival poisson rate must be > 0, got {rate}")
        return rate
    raise SystemExit(f'--arrival must be "asap" or "poisson:RATE", got {spec!r}')


def drive_load(server, app: str, *, requests: int, batch: int,
               concurrency: int, rate=None, seed: int = 0):
    """The load generator: submit ``requests`` requests of ``batch``
    samples to a started server, at most ``concurrency`` outstanding,
    back to back (``rate=None``) or at Poisson ``rate`` requests/s, and
    wait for every one. Returns ``(handles, seconds)``."""
    arrival_rng = np.random.default_rng(seed)
    handles = []
    t_load = time.perf_counter()
    for _r in range(requests):
        outstanding = [h for h in handles if not h.done()]
        while len(outstanding) >= max(1, concurrency):
            outstanding[0].wait()
            outstanding = [h for h in outstanding if not h.done()]
        handles.append(server.submit(app, batch=batch))
        if rate is not None:
            time.sleep(arrival_rng.exponential(1.0 / rate))
    for h in handles:
        h.wait()
    return handles, time.perf_counter() - t_load


def exit_on_failed(handles) -> None:
    """Exit non-zero, printing the first error, when any accepted request
    ended ``failed``: the server keeps serving past a failed dispatch, so
    its caller must look."""
    from ..core.serving import FAILED

    failed = [h for h in handles if h.status == FAILED]
    if failed:
        h = failed[0]
        traceback.print_exception(h.error, file=sys.stderr)
        raise SystemExit(
            f"{len(failed)}/{len(handles)} request(s) failed; first, request "
            f"{h.id} ({h.app}): {type(h.error).__name__}: {h.error}"
        )


def serve_cosim(args) -> None:
    from ..core import ila
    from ..core.serving import CosimServer, build_app, percentiles_ms
    from ..core.telemetry import TELEMETRY
    from .jax_cache import enable_compile_cache

    enable_compile_cache()
    if args.trace:
        # span recording (Perfetto export at exit); metrics counters are
        # always on — this only turns on the timed-region ring buffer
        TELEMETRY.enable()

    try:
        res, params = build_app(args.cosim)
    except KeyError as e:
        raise SystemExit(e.args[0]) from None
    print(f"compiled {args.cosim}: offloads={res.accelerator_calls} "
          f"policy={res.stats['extraction']['policy']}")
    mesh = ila.set_stream_mesh(args.mesh) if args.mesh != "off" else None
    if args.mesh != "off":
        print(f"stream mesh: {mesh if mesh is not None else 'disabled (single device host)'}")

    # the chunk size is clamped so even the default --batch splits into
    # >= 2 pack/sim chunks per node — a single-chunk batch has nothing for
    # the pipeline to overlap
    rate = _parse_arrival(args.arrival)
    server = CosimServer(
        engine=args.engine,
        devices_per_target=args.devices_per_target,
        pipeline_chunk=max(1, min(8, -(-args.batch // 2))),
        queue_depth=args.queue_depth,
        max_batch=args.max_batch or max(4 * args.batch, 8),
        coalesce=not args.no_coalesce,
        overlap=not args.no_overlap,
        seed=args.seed,
    )
    server.add_program(args.cosim.lower(), res.program, params)
    ex = server.executor
    engine = ex.engine  # --engine > REPRO_ENGINE > pipelined

    warmup = max(args.warmup, 1)
    t0 = time.perf_counter()
    server.start(warmup=warmup, warm_batch=args.batch)
    warm_s = time.perf_counter() - t0
    cold_ms = warm_s / (warmup * args.batch) * 1e3
    print(f"warmup: {warmup} request(s) x batch {args.batch} in {warm_s:.3f}s "
          f"({cold_ms:.1f} ms/sample incl. compile+traces) "
          f"-> serving on {engine}")

    handles, load_s = drive_load(
        server, args.cosim.lower(), requests=args.requests, batch=args.batch,
        concurrency=args.concurrency, rate=rate, seed=args.seed)
    server.close(drain=True)
    exit_on_failed(handles)

    served = [h for h in handles if h.status == "done"]
    rejected = [h for h in handles if h.rejected]
    print(f"load: {len(served)}/{len(handles)} served, "
          f"{len(rejected)} rejected "
          f"({args.arrival}, concurrency {args.concurrency}, "
          f"queue depth {args.queue_depth})")

    # steady-state stats — guarded: with --requests 0 (or every request
    # rejected / a ~0s warm request) there is nothing to ratio against
    if served and load_s > 0:
        lats = [h.latency_s for h in served]
        pct = percentiles_ms(lats)
        qps = len(served) / load_s
        warm_ms = float(np.mean(lats)) / args.batch * 1e3
        print(f"sustained: {qps:.1f} req/s ({qps * args.batch:.1f} samples/s) "
              f"| latency p50 {pct['p50_ms']:.1f} / p95 {pct['p95_ms']:.1f} "
              f"/ p99 {pct['p99_ms']:.1f} ms")
        summ = server.summary()
        print(f"coalescing: {summ['batches']} dispatch batch(es), "
              f"mean {summ['mean_batch']:.1f} req/batch, "
              f"max {summ['coalesced_max']}")
        if warm_ms > 0 and np.isfinite(warm_ms) and np.isfinite(cold_ms):
            print(f"cold vs steady state: {cold_ms:.1f} ms/sample (warmup) "
                  f"vs {warm_ms:.1f} ms/sample (mean of "
                  f"{len(served)} served, {engine}) "
                  f"-> {cold_ms / warm_ms:.1f}x")
    else:
        print("no measured requests (0 requested or all rejected); "
              "skipping steady-state stats")

    print("\nper-target summary (devices: jobs / est cycles / utilization):")
    for tname, row in sorted(ex.stats_summary().items()):
        devs = row.pop("devices", {})
        print(f"  {tname}: invocations={row['invocations']} "
              f"commands={row['commands']} est_cycles={row['est_cycles']:.0f} "
              f"max_rel_err={row['max_rel_err']:.4f}")
        for dname, d in sorted(devs.items()):
            print(f"    {dname}: jobs={d['jobs']} groups={d['groups']} "
                  f"est_cycles={d['est_cycles']:.0f} "
                  f"utilization={d['utilization']:.2f}")
    if engine in ("pipelined", "fused"):
        stages = ex.pipeline_summary()
        print("pipeline stages (measured requests): "
              f"pack {stages['pack_s']:.3f}s / dispatch {stages['dispatch_s']:.3f}s "
              f"/ readback {stages['readback_s']:.3f}s "
              f"(overlap ~{stages['overlap_s']:.3f}s)")
    print("\ncache health:", ex.cache_info())

    # request-level drift: the ratio admission control ran under
    # (docs/observability.md, "Drift probes")
    dr = server.metrics.find("serving.drift_ratio")
    if dr and dr[0].snapshot()["count"]:
        s = dr[0].snapshot()
        print(f"admission drift (service us / priced cycles): "
              f"p50 {s['p50']:.2f} p95 {s['p95']:.2f} (n={s['count']})")

    if args.trace:
        path = TELEMETRY.export_trace(args.trace)
        print(f"trace: {TELEMETRY.spans_recorded} span(s) "
              f"({TELEMETRY.spans_dropped} dropped) -> {path} "
              f"(open in https://ui.perfetto.dev or chrome://tracing)")
    if args.metrics:
        bad = TELEMETRY.check_names()
        assert not bad, f"metric names violate the documented schema: {bad}"
        print(f"metrics: -> {TELEMETRY.export_metrics(args.metrics)}")
    if mesh is not None:
        ila.set_stream_mesh(None)


def serve_llm(args) -> None:
    from ..configs import get_config, get_smoke_config
    from ..models import api

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    B = args.batch
    max_len = args.prompt + args.gen
    cache = api.init_cache(cfg, B, max_len)

    if cfg.family == "audio":
        frames = jnp.asarray(
            rng.standard_normal((B, api.AUDIO_ENC_FRAMES, cfg.d_model)), jnp.bfloat16)
        t0 = time.perf_counter()
        _, cache = api.prefill(cfg, params, frames, cache)
        tok = jnp.zeros((B, 1), jnp.int32)
        start = 0
    else:
        prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, args.prompt)), jnp.int32)
        t0 = time.perf_counter()
        logits, cache = api.prefill(cfg, params, prompt, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        start = args.prompt
    _force(tok, cache)
    print(f"prefill: {time.perf_counter()-t0:.2f}s")

    outs = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        logits, cache = api.decode_step(cfg, params, cache, tok, start + i)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        outs.append(tok)
    _force(tok, cache)
    dt = time.perf_counter() - t0
    gen = np.concatenate([np.asarray(t) for t in outs], axis=1)
    print(f"decode: {args.gen-1} steps x{B} in {dt:.2f}s ({dt/(args.gen-1)*1e3:.0f} ms/step)")
    print(gen)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="LLM decode mode: model config name")
    ap.add_argument("--cosim", default=None,
                    help="co-sim serving mode: application name (repro.core.apps)")
    ap.add_argument("--devices-per-target", type=int, default=1,
                    help="simulated device instances per accelerator target")
    ap.add_argument("--engine", default=None,
                    choices=["pipelined", "fused", "jit", "eager"],
                    help="co-sim engine for warmup and measured requests "
                         "(default: REPRO_ENGINE or pipelined)")
    ap.add_argument("--mesh", default="off",
                    help='"off" (default), "auto" (all host devices) or an '
                         "int: shard the vmapped batch axis over a device mesh")
    ap.add_argument("--warmup", type=int, default=1,
                    help="warmup requests excluded from steady-state stats")
    ap.add_argument("--requests", type=int, default=16,
                    help="measured requests the load generator submits")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="load generator: max outstanding requests")
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="server admission control: max queued requests "
                         "(beyond this, submissions are rejected)")
    ap.add_argument("--arrival", default="asap",
                    help='"asap" (back-to-back) or "poisson:RATE" '
                         "(exponential inter-arrival gaps, RATE req/s)")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="coalescing cap in samples per dispatch "
                         "(0: 4x --batch)")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="disable cross-request coalescing (serial baseline)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="drain the pipeline at every request's assemble "
                         "barrier (pre-serving baseline)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record telemetry spans and export a Perfetto/"
                         "chrome://tracing trace_event JSON at exit")
    ap.add_argument("--metrics", default=None, metavar="OUT.json",
                    help="export a JSON snapshot of every telemetry metric "
                         "(counters/gauges/histograms) at exit")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.cosim is not None:
        serve_cosim(args)
    elif args.arch is not None:
        serve_llm(args)
    else:
        ap.error("one of --arch (LLM decode) or --cosim (co-sim serving) is required")


if __name__ == "__main__":
    main()
