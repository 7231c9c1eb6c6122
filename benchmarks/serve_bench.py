"""Serving benchmark: measured steady-state FPS of the jitted batched
executor vs (a) the eager per-sample loop and (b) the Algorithm-1 modeled
pipeline FPS — all from the same compiled :class:`EngineProgram` — written
to one JSON artifact (``BENCH_serve.json``, uploaded by the CI bench-smoke
job).

  PYTHONPATH=src:. python benchmarks/serve_bench.py --quick   # CI setting
  PYTHONPATH=src:. python benchmarks/serve_bench.py           # full sweep
"""

from __future__ import annotations

import argparse
import json
import platform
import time

import jax

from benchmarks.table1 import modeled_row
from repro.core import workload as W
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_cnn import serve

SCHEMA_VERSION = 1
DEFAULT_OUT = "BENCH_serve.json"


def bench_model(model: str, *, batch: int, frames: int,
                eager_frames: int, seed: int = 0) -> dict:
    """One model: serve a synthetic stream through the jitted executor,
    time the eager reference loop, and attach the analytic Table-I row.
    ``seed`` pins the params/calibration/stream RNGs explicitly so the
    measured-vs-modeled rows are reproducible run to run."""
    measured = serve(model, frames=frames, batch=batch, seed=seed,
                     eager_frames=eager_frames, verbose=True)
    measured["modeled"] = {
        k: (round(v, 4) if isinstance(v, float) else v)
        for k, v in modeled_row(model).items()}
    return measured


def run(emit, *, quick: bool = False, batch: int | None = None,
        out: str = DEFAULT_OUT, models: list[str] | None = None,
        seed: int = 0) -> dict:
    if models is None:
        models = ["alexnet"] if quick else list(W.CNN_MODELS)
    if batch is None:
        batch = 8 if quick else 32
    frames = 3 * batch
    eager_frames = 2 if quick else 4
    data: dict = {
        "schema_version": SCHEMA_VERSION,
        "bench": "serve",
        "quick": quick,
        "batch": batch,
        "seed": seed,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_version": jax.__version__,
        "backend": jax.devices()[0].platform,
        "host": platform.machine(),
        "models": {},
    }
    for model in models:
        r = bench_model(model, batch=batch, frames=frames,
                        eager_frames=eager_frames, seed=seed)
        data["models"][model] = r
        emit(f"serve/{model}/batched_fps", 0.0,
             f"{r['measured_steady_fps']}fps|batch={batch}")
        emit(f"serve/{model}/eager_fps", 0.0, f"{r['eager_fps']}fps")
        emit(f"serve/{model}/speedup_vs_eager", 0.0,
             f"{r['speedup_vs_eager']}x")
        emit(f"serve/{model}/modeled_fps_alg1", 0.0,
             f"{r['modeled_fps_alg1']}fps")
    with open(out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"\n[serve_bench] wrote {out} "
          f"({len(data['models'])} model(s), batch {batch})")
    return data


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="AlexNet only, small batch (CI bench-smoke)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="explicit params/calibration/stream RNG seed "
                         "(recorded in the artifact)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--model", action="append", default=None,
                    choices=sorted(W.CNN_MODELS), dest="models")
    args = ap.parse_args(argv)
    from benchmarks.run import print_csv
    csv: list[str] = []

    def emit(name, us, derived=""):
        csv.append(f"{name},{us:.1f},{derived}")

    run(emit, quick=args.quick, batch=args.batch, out=args.out,
        models=args.models, seed=args.seed)
    print_csv(csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
