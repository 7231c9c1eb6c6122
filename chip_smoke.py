"""Serve the paper's YOLO at full width on a TPU and check it bit for bit.

    python chip_smoke.py              # one chip: Server + golden + kernel
    python chip_smoke.py --chips 4    # four one-chip replicas vs one

One process drives the main serving path through its user entry points:
``ProgramRegistry`` compiles YOLO (448x448, published channel counts,
seeded random weights) on the host, ``build_server`` brings up a
2-stage pipeline at batch 8, ``Server.submit`` sends a few dozen frames,
and every request is waited on. The checks, all bit for bit:

(a) the served route's whole-chain runner reproduces
    ``tests/golden/yolo.npz`` (frozen exponents, accumulator sample and
    crc, top-1) on the two golden frames;
(b) every served request's logits equal that runner's logits for the
    same frame;
(c) the Pallas int8 kernel route reproduces the same golden.

``--chips 4`` runs only the replica path: ``replicas=4``,
``replica_mode="pipeline"`` against ``replicas=1`` on the same frames,
and checks that the outputs are equal, that each replica's stages and
weights sit on their own chip, that every replica served routed batches
over the server's life, and that each replica fed directly matches.

Without a TPU it exits non-zero before doing any work. The last line of
standard output is the JSON result, printed only when every check
passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MODEL = "yolo"
BATCH = 8
STAGES = 2
SEED = 0
GOLDEN = ROOT / "tests" / "golden" / f"{MODEL}.npz"


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")
    log(f"ok: {what}")


def serve_frames(server, frames) -> np.ndarray:
    """Submit every frame through ``Server.submit``, wait on each."""
    reqs = [server.submit(MODEL, f) for f in frames]
    return np.stack([r.result(timeout=600) for r in reqs])


def reference_logits(prog, frames):
    """Whole-chain runner on the served route, batch by batch: raw int32
    accumulators and logits. The first batch's time includes the
    compile."""
    runner = prog.compile_runner()
    accs = []
    for i in range(0, len(frames), BATCH):
        t0 = time.perf_counter()
        accs.append(np.asarray(runner(runner.quantize(frames[i:i + BATCH]))))
        if i == 0:
            log(f"whole-chain runner (route={runner.route}): first batch "
                f"(compile + run) {time.perf_counter() - t0:.3f}s")
    acc = np.concatenate(accs)
    return runner, acc, runner.dequantize(acc)


def one_chip(prog, n_requests: int) -> None:
    from repro.compiler import (assert_golden, check_golden, golden_frames,
                                golden_record, load_golden)
    from repro.serving import ServerConfig, build_server
    from repro.serving.server import ProgramRegistry, synthetic_stream

    golden = load_golden(GOLDEN)
    frames = synthetic_stream(MODEL, n_requests, SEED)
    check(np.array_equal(frames[:2], golden_frames(prog.model, seed=SEED)),
          "the first two served frames are the golden frames")

    runner, acc, want = reference_logits(prog, frames)
    assert_golden(golden_record(runner, acc[:2]), golden,
                  f"{MODEL} route={runner.route!r} on the chip")
    log(f"ok: (a) route {runner.route!r} reproduces {GOLDEN.name}")

    reg = ProgramRegistry()
    reg.register(MODEL, prog)
    t0 = time.perf_counter()
    server = build_server(reg, ServerConfig(stages=STAGES, batch=BATCH,
                                            output="logits", seed=SEED))
    try:
        log(f"build_server (K={STAGES}, batch={BATCH}): "
            f"{time.perf_counter() - t0:.3f}s, of which stage compiles + "
            f"first pass {server.runtime(MODEL).warmup_s:.3f}s")
        t0 = time.perf_counter()
        got = serve_frames(server, frames)
        log(f"served {len(got)} requests in "
            f"{time.perf_counter() - t0:.3f}s")
    finally:
        server.close()
    check(got.shape == want.shape and np.array_equal(got, want),
          f"(b) {len(got)} served logits equal the whole-chain runner's")

    t0 = time.perf_counter()
    check_golden(prog, golden, seed=SEED, route="kernel")
    log(f"ok: (c) Pallas kernel route reproduces {GOLDEN.name} "
        f"({time.perf_counter() - t0:.3f}s)")


def four_chips(prog, devices, n_requests: int) -> None:
    import jax

    from repro.serving import ServerConfig, build_server
    from repro.serving.server import ProgramRegistry, synthetic_stream

    frames = synthetic_stream(MODEL, n_requests, SEED)
    outs, direct = {}, []
    for replicas in (4, 1):
        reg = ProgramRegistry()
        reg.register(MODEL, prog)
        t0 = time.perf_counter()
        server = build_server(reg, ServerConfig(
            stages=STAGES, batch=BATCH, output="logits", seed=SEED,
            replicas=replicas, replica_mode="pipeline"))
        try:
            log(f"build_server (R={replicas}): "
                f"{time.perf_counter() - t0:.3f}s, of which stage compiles "
                f"+ first pass {server.runtime(MODEL).warmup_s:.3f}s")
            pool = server.runtime(MODEL).executor
            before = pool.replica_counts() if replicas > 1 else None
            outs[replicas] = serve_frames(server, frames)
            if replicas == 1:
                continue
            # Routed batches over the server's life: build_server's
            # calibration stream and the requests both go through the
            # router. Least-wait routing may leave a replica idle when
            # fewer suffice, so the request phase alone proves nothing.
            after = pool.replica_counts()
            placed = []
            for r, rep in enumerate(pool.replicas):
                devs = {d for run in rep.runners
                        for leaf in jax.tree.leaves(run.weights)
                        for d in leaf.devices()}
                pins = {run.device for run in rep.runners}
                routed = after[r]["completed_batches"]
                log(f"replica {r}: stages pinned to "
                    f"{sorted(map(str, pins))}, weights on "
                    f"{sorted(map(str, devs))}; routed batches served "
                    f"{routed} (requests: "
                    f"{routed - before[r]['completed_batches']})")
                check(devs == pins and len(pins) == 1,
                      f"replica {r}'s stages and weights share one chip")
                check(routed > 0, f"replica {r} served routed batches")
                placed.append(pins.pop())
                direct.append(np.stack(rep.serve(list(frames[:BATCH]))))
            check(len(set(placed)) == 4 and set(placed) <= set(devices),
                  "the four replicas sit on four distinct chips")
        finally:
            server.close()
    check(np.array_equal(outs[4], outs[1]),
          f"{n_requests} routed logits from R=4 equal R=1 bit for bit")
    check(all(np.array_equal(d, outs[1][:BATCH]) for d in direct),
          "each replica, fed directly, equals R=1 bit for bit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.server import compile_for_serving

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}; "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")

    t0 = time.perf_counter()
    prog = compile_for_serving(MODEL, seed=SEED)
    log(f"compile {MODEL} on the host: {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(prog, n_requests=32)
    else:
        four_chips(prog, devices[:4], n_requests=64)
    log(f"device phases: {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
