"""Async serving benchmark: single-jit vs stage-pipelined serving.

For each model, compiles one :class:`EngineProgram` and serves the same
seeded synthetic stream through the K-stage software pipeline
(``repro.serving``) for K in ``--stages`` (default 1, 2, 4): closed-loop
steady-state throughput, then open-loop request latency (p50/p95/p99)
through the async frontend at a sustainable arrival rate. K=1 is the
single-jit baseline (one stage == ``compile_runner``'s whole chain), so
``throughput_vs_single_jit`` reads the cost/benefit of pipelining
directly. Results land in one JSON artifact (``BENCH_serve_async.json``,
built, validated and uploaded by the CI bench-smoke job).

The open-loop stream comes from the one seeded synthetic-traffic
generator (``repro.serving.traffic.make_schedule`` via ``serve_async``)
that ``serve_qos_bench.py`` also replays; the recorded ``seed`` field
reproduces the exact arrival schedule and frames.

  PYTHONPATH=src:. python benchmarks/serve_async_bench.py --quick  # CI
  PYTHONPATH=src:. python benchmarks/serve_async_bench.py          # full
"""

from __future__ import annotations

import argparse
import json
import platform
import time

import jax

from repro.core import workload as W
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_cnn import compile_for_serving, serve_async

SCHEMA_VERSION = 1
DEFAULT_OUT = "BENCH_serve_async.json"
DEFAULT_STAGES = (1, 2, 4)


def bench_model(model: str, *, batch: int, frames: int | None,
                stage_counts: tuple[int, ...], seed: int,
                max_wait_ms: float | None) -> dict:
    """One model: sweep stage counts over one compiled program. Without
    an explicit ``frames``, each K measures ``(4 + 2K)`` micro-batches —
    a deeper pipeline needs a longer stream for its fill/drain ramps to
    amortize out of the steady-state window."""
    prog = compile_for_serving(model, bits=8, seed=seed)
    row: dict = {
        "modeled_fps_alg1": round(prog.fps(), 3),
        "stages": {},
    }
    for k in stage_counts:
        n = frames if frames is not None else (4 + 2 * k) * batch
        r = serve_async(model, frames=n, batch=batch, stages=k,
                        seed=seed, max_wait_ms=max_wait_ms, program=prog,
                        verbose=True)
        row["stages"][str(k)] = r
    # Normalize against the true single-jit baseline (K=1), not whatever
    # ran first; the field is omitted when a custom --stages sweep has
    # no K=1 run to compare against.
    base = row["stages"].get("1")
    if base is not None:
        base_fps = max(base["measured_steady_fps"], 1e-9)
        for r in row["stages"].values():
            r["throughput_vs_single_jit"] = round(
                r["measured_steady_fps"] / base_fps, 4)
    return row


def run(emit, *, quick: bool = False, batch: int | None = None,
        frames: int | None = None, out: str = DEFAULT_OUT,
        models: list[str] | None = None,
        stage_counts: tuple[int, ...] = DEFAULT_STAGES,
        seed: int = 0, max_wait_ms: float | None = None) -> dict:
    if models is None:
        models = ["alexnet"] if quick else list(W.CNN_MODELS)
    if batch is None:
        batch = 8 if quick else 32
    data: dict = {
        "schema_version": SCHEMA_VERSION,
        "bench": "serve_async",
        "quick": quick,
        "batch": batch,
        "frames": frames,          # null = per-K default (4 + 2K batches)
        "seed": seed,
        "stage_counts": list(stage_counts),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_version": jax.__version__,
        "backend": jax.devices()[0].platform,
        "host": platform.machine(),
        "models": {},
    }
    for model in models:
        row = bench_model(model, batch=batch, frames=frames,
                          stage_counts=stage_counts, seed=seed,
                          max_wait_ms=max_wait_ms)
        data["models"][model] = row
        for k, r in row["stages"].items():
            vs_k1 = r.get("throughput_vs_single_jit")
            emit(f"serve_async/{model}/K{k}/steady_fps", 0.0,
                 f"{r['measured_steady_fps']}fps"
                 + (f"|x{vs_k1}_vs_K1" if vs_k1 is not None else ""))
            emit(f"serve_async/{model}/K{k}/latency_p99", 0.0,
                 f"{r['latency_ms_p99']}ms|p50={r['latency_ms_p50']}ms|"
                 f"arrival={r['arrival_fps']}fps")
    with open(out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"\n[serve_async_bench] wrote {out} ({len(data['models'])} "
          f"model(s), batch {batch}, K in {list(stage_counts)})")
    return data


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="AlexNet only, small batch (CI bench-smoke)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--frames", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="params/calibration/stream RNG seed")
    ap.add_argument("--stages", type=int, action="append", default=None,
                    dest="stage_counts",
                    help="stage count to sweep (repeatable; default 1 2 4)")
    ap.add_argument("--max-wait-ms", type=float, default=None,
                    help="batcher flush timeout (default: one full-batch "
                         "window at the arrival rate)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--model", action="append", default=None,
                    choices=sorted(W.CNN_MODELS), dest="models")
    args = ap.parse_args(argv)
    from benchmarks.run import print_csv
    csv: list[str] = []

    def emit(name, us, derived=""):
        csv.append(f"{name},{us:.1f},{derived}")

    run(emit, quick=args.quick, batch=args.batch, frames=args.frames,
        out=args.out, models=args.models, seed=args.seed,
        stage_counts=tuple(args.stage_counts or DEFAULT_STAGES),
        max_wait_ms=args.max_wait_ms)
    print_csv(csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
