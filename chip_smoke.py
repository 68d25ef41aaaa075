#!/usr/bin/env python3
"""Bring-up smoke run of the 3LA co-simulation serving path on a TPU.

Drives the path a user drives — ``build_app`` (flexible matching via
``compile_program``) -> ``CosimServer`` -> ``Executor`` — the same code as
``python -m repro.launch.serve --cosim``, for LSTM-WLM (FlexASR linear +
LSTM), ResNet-20 (HLSCNN conv2d) and Transformer (FlexASR attention), each
at the size ``core/apps.py`` builds it, with a few requests of 16 samples
each on the ``fused`` engine (Pallas legs) and on ``pipelined``.

Checks, any of which failing exits non-zero before the last line:

  (a) JAX's first device is a TPU (never carries on on the CPU);
  (b) every request ends done: none failed, none rejected;
  (c) the fused FlexASR linear and HLSCNN conv runners lowered to Pallas
      with interpret mode off;
  (d) fused and pipelined outputs agree per request id: bit-exact where
      every fused runner the app used replicates the compiled arithmetic,
      else within the largest declared tolerance of the app's intrinsics;
  (e) the same requests, replayed by request id on the host CPU backend in
      this process, agree with the chip within the targets' ``cosim_tol``.

    python chip_smoke.py             # one chip: the phases above
    python chip_smoke.py --chips 4   # only: ResNet-20 served with the
                                     # stream mesh over four chips vs the
                                     # same request ids on one chip,
                                     # bit-exact

The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

APPS = ("LSTM-WLM", "ResNet-20", "Transformer")
MESH_APP = "ResNet-20"
ENGINES = ("pipelined", "fused")
REQUESTS = 3
BATCH = 16
SEED = 0


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def tpu_devices(chips: int):
    """(a): the devices, or exit at once when JAX finds no TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device: "
                 f"{devs[0].platform}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return devs


def app_ops(program):
    """The accelerator intrinsics an extracted program invokes."""
    from repro.core import ir

    return sorted({n.op for n in ir.postorder(program)
                   if isinstance(n, ir.Call) and ir.accel_op_target(n.op)})


def serve(name, program, params, engine):
    """Serve REQUESTS requests of BATCH samples on a fresh server; returns
    the outputs by request id plus the phase's timings and cache health."""
    from repro.core.serving import DONE, CosimServer
    from repro.launch.serve import drive_load, exit_on_failed

    key = name.lower()
    server = CosimServer(engine=engine, max_batch=BATCH, seed=SEED)
    server.add_program(key, program, params)
    t0 = time.perf_counter()
    server.start(warmup=1, warm_batch=BATCH)
    cold_s = time.perf_counter() - t0
    handles, load_s = drive_load(server, key, requests=REQUESTS, batch=BATCH,
                                 concurrency=REQUESTS, seed=SEED)
    server.close(drain=True)
    exit_on_failed(handles)                                           # (b)
    bad = [(h.id, h.status, h.reject_reason) for h in handles
           if h.status != DONE]
    check(not bad, f"(b) {name}/{engine}: requests not done: {bad}")
    return {
        "server": server,
        "outs": {h.id: np.stack(h.outputs) for h in handles},
        "cold_s": cold_s,
        "ms_per_request": load_s / REQUESTS * 1e3,
        # targets this app touched (the registry holds all four)
        "cache": {t: c for t, c in server.executor.cache_info().items()
                  if c["fragments"]["size"]},
    }


def compare(ref: dict, got: dict):
    """(max abs error, max per-sample rel-Frobenius error) over request
    ids and samples."""
    from repro.core.validate import frob_rel_err

    check(ref.keys() == got.keys(), "request ids differ between runs")
    abs_err = max(float(np.max(np.abs(ref[r] - got[r]))) for r in ref)
    rel_err = max(frob_rel_err(a, b) for r in ref
                  for a, b in zip(ref[r], got[r]))
    return abs_err, rel_err


def cpu_replay(server, name, program, ids):
    """(e): the same request ids, operands rebuilt by request_envs, on the
    host CPU backend with the compiled engine (the bit-exactness oracle)."""
    import jax

    from repro.core.codegen import Executor

    cpu = jax.devices("cpu")[0]
    outs = {}
    with jax.default_device(cpu):
        ex = Executor("ila", engine="compiled")
        for rid in ids:
            got = ex.run_many(program, server.request_envs(name.lower(), rid,
                                                           BATCH))
            for v in got:
                if isinstance(v, jax.Array):
                    check(v.devices() == {cpu},
                          f"(e) {name}: replay output not on the CPU")
            outs[rid] = np.stack([np.asarray(v) for v in got])
    return outs


def fused_runners():
    from repro.core.ila import TARGETS

    return [r for t in TARGETS.all() for r in t.fused_runners()]


def one_chip() -> None:
    from repro.core.ila import TARGETS
    from repro.core.serving import build_app

    pallas_seen = set()
    for name in APPS:
        res, params = build_app(name)
        program = res.program
        ops = app_ops(program)
        print(f"{name}: offloads={res.accelerator_calls} intrinsics={ops}")
        runs = {}
        for engine in ENGINES:
            before = {id(r) for r in fused_runners()}
            runs[engine] = serve(name, program, params, engine)
            runs[engine]["runners"] = [r for r in fused_runners()
                                       if id(r) not in before]
        runners = runs["fused"]["runners"]
        for r in runners:                                             # (c)
            print(f"  fused runner {r.name}: lowering={r.lowering} "
                  f"interpret={r.interpret} exact={r.exact}")
            check(not r.interpret, f"(c) {r.name} built in interpret mode")
            if r.lowering == "pallas":
                pallas_seen.add(r.name)
        # (d) fused vs pipelined, by request id
        tols = {op: TARGETS.intrinsic(op)[1].tol for op in ops}
        bound_d = 0.0 if all(r.exact for r in runners) else max(tols.values())
        d_abs, d_rel = compare(runs["pipelined"]["outs"], runs["fused"]["outs"])
        # (e) chip vs host CPU, by request id
        bound_e = max(TARGETS.intrinsic(op)[0].cosim_tol(ops) for op in ops)
        ref = cpu_replay(runs["pipelined"]["server"], name, program,
                         sorted(runs["pipelined"]["outs"]))
        for engine in ENGINES:
            run = runs[engine]
            e_abs, e_rel = compare(ref, run["outs"])
            line = (f"  {name} [{engine}]: cold start {run['cold_s']:.3f} s "
                    f"(warmup incl. compiles), steady {run['ms_per_request']:.3f}"
                    f" ms/request ({REQUESTS} x {BATCH} samples); vs CPU "
                    f"max abs {e_abs:.3e} max rel {e_rel:.3e} "
                    f"(bound {bound_e})")
            if engine == "fused":
                line += (f"; vs pipelined max abs {d_abs:.3e} max rel "
                         f"{d_rel:.3e} (bound {bound_d})")
            print(line)
            print(f"    cache_info {json.dumps(run['cache'], sort_keys=True)}")
            check(e_rel <= bound_e,
                  f"(e) {name}/{engine}: chip vs CPU rel err {e_rel} > {bound_e}")
        check(d_rel <= bound_d if bound_d else d_abs == 0.0,
              f"(d) {name}: fused vs pipelined rel {d_rel} abs {d_abs} "
              f"> bound {bound_d}")
    for want in ("flexasr-linear-pallas", "hlscnn-conv2d-pallas"):
        check(want in pallas_seen, f"(c) no {want} runner was served")
    print(f"(c) Pallas legs served compiled: {sorted(pallas_seen)}")
    print("note: every SimDevice dispatches to jax.devices()[0] (ROADMAP "
          "S8/D3); only the stream mesh spreads work across chips")


def four_chips() -> None:
    from repro.core import ila
    from repro.core.serving import build_app

    res, params = build_app(MESH_APP)
    mesh = ila.set_stream_mesh(4)
    check(mesh is not None, "stream mesh over 4 devices was not built")
    probe = ila._shard_batched(np.zeros((BATCH, 1), np.float32))
    print(f"stream mesh {dict(mesh.shape)} over "
          f"{[str(d) for d in mesh.devices.flat]}")
    print("sharded operand rows per device: " + ", ".join(
        f"{s.device}: rows {s.index[0].start}:{s.index[0].stop}"
        for s in probe.addressable_shards))
    try:
        sharded = serve(MESH_APP, res.program, params, "fused")
    finally:
        ila.set_stream_mesh(None)
    single = serve(MESH_APP, res.program, params, "fused")
    d_abs, d_rel = compare(single["outs"], sharded["outs"])
    for label, run in (("mesh x4", sharded), ("one chip", single)):
        print(f"  {MESH_APP} [fused, {label}]: cold start {run['cold_s']:.3f}"
              f" s, steady {run['ms_per_request']:.3f} ms/request")
    print(f"  mesh x4 vs one chip: max abs {d_abs:.3e} max rel {d_rel:.3e}")
    check(d_abs == 0.0, "stream-mesh outputs differ from one chip")
    print("note: every SimDevice dispatches to jax.devices()[0] (ROADMAP "
          "S8/D3); only the stream mesh spreads work across chips")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the stream-mesh comparison over four chips")
    args = ap.parse_args()

    devs = tpu_devices(args.chips)                                    # (a)
    from repro.launch.jax_cache import enable_compile_cache

    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"compile cache {enable_compile_cache()}")
    try:
        four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
