"""QoS serving benchmark: mixed traffic classes under two arrival rates.

For each model, compiles one :class:`EngineProgram`, measures the
pipeline's steady-state throughput, then replays the same seeded
mixed-class schedule (``repro.serving.traffic`` — the generator
``serve_async_bench`` shares) open-loop at two load factors, one below
saturation and one above. The artifact (``BENCH_serve_qos.json``, built,
validated and uploaded by the CI bench-smoke job) records, per class and
per rate: the queueing / assembly / compute latency split (p50/p95/p99),
the SLO miss rate, and the drop rate — the numbers that show priority
lanes protecting the interactive class while the best-effort class
absorbs the overload.

  PYTHONPATH=src:. python benchmarks/serve_qos_bench.py --quick  # CI
  PYTHONPATH=src:. python benchmarks/serve_qos_bench.py          # full
"""

from __future__ import annotations

import argparse
import json
import platform
import time

import jax

from repro.core import workload as W
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_cnn import compile_for_serving, serve_qos
from repro.serving import parse_traffic_mix

SCHEMA_VERSION = 1
DEFAULT_OUT = "BENCH_serve_qos.json"
DEFAULT_LOAD_FACTORS = (0.6, 1.2)


def bench_model(model: str, *, batch: int, frames: int | None,
                stages: int, seed: int, slo_ms: float | None,
                traffic_mix, load_factors: tuple[float, ...],
                place_stages: bool, poisson: bool,
                admission_control: bool,
                flush_guard_ms: float | None) -> dict:
    """One model: throughput phase + one open-loop mixed-traffic replay
    per load factor, over one compiled program."""
    prog = compile_for_serving(model, bits=8, seed=seed)
    n = frames if frames is not None else (6 + 2 * stages) * batch
    return serve_qos(model, frames=n, batch=batch, stages=stages,
                     seed=seed, slo_ms=slo_ms, traffic_mix=traffic_mix,
                     load_factors=load_factors, place_stages=place_stages,
                     poisson=poisson, admission_control=admission_control,
                     flush_guard_ms=flush_guard_ms,
                     program=prog, verbose=True)


def run(emit, *, quick: bool = False, batch: int | None = None,
        frames: int | None = None, out: str = DEFAULT_OUT,
        models: list[str] | None = None, stages: int = 2,
        seed: int = 0, slo_ms: float | None = None,
        traffic_mix_spec: str | None = None,
        load_factors: tuple[float, ...] = DEFAULT_LOAD_FACTORS,
        place_stages: bool = False, poisson: bool = False,
        admission_control: bool = True,
        flush_guard_ms: float | None = None) -> dict:
    if models is None:
        models = ["alexnet"] if quick else list(W.CNN_MODELS)
    if batch is None:
        batch = 8 if quick else 32
    # slo_ms may be None (serve_qos derives a feasible deadline from
    # measured service time); parse_traffic_mix then refuses the 'slo'
    # token rather than arming a 0 ms deadline.
    mix = (parse_traffic_mix(traffic_mix_spec, slo_ms)
           if traffic_mix_spec else None)
    data: dict = {
        "schema_version": SCHEMA_VERSION,
        "bench": "serve_qos",
        "quick": quick,
        "batch": batch,
        "frames": frames,          # null = per-model default
        "stages": stages,
        "seed": seed,              # one seed drives params, calibration,
        "slo_ms": slo_ms,          # frames AND the arrival schedule —
        "poisson": poisson,        # the artifact replays bit-for-bit
        "load_factors": list(load_factors),
        "place_stages": place_stages,
        # The control-plane config behind these numbers, recorded so the
        # knee and qos artifacts are comparable across PRs (per-rate
        # rows additionally carry the live estimator state as
        # "control").
        "admission_control": admission_control,
        "flush_guard_ms": flush_guard_ms,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_version": jax.__version__,
        "backend": jax.devices()[0].platform,
        "host": platform.machine(),
        "models": {},
    }
    for model in models:
        row = bench_model(model, batch=batch, frames=frames, stages=stages,
                          seed=seed, slo_ms=slo_ms, traffic_mix=mix,
                          load_factors=load_factors,
                          place_stages=place_stages, poisson=poisson,
                          admission_control=admission_control,
                          flush_guard_ms=flush_guard_ms)
        data["models"][model] = row
        for rate_key, rrow in row["rates"].items():
            for name, crow in rrow["classes"].items():
                q = crow["phase_ms"]["queueing"]["p95"]
                a = crow["phase_ms"]["assembly"]["p95"]
                c = crow["phase_ms"]["compute"]["p95"]
                emit(f"serve_qos/{model}/{rate_key}/{name}", 0.0,
                     f"p95_q={q}ms|a={a}ms|c={c}ms|"
                     f"miss={crow['slo_miss_rate']}|"
                     f"drop={crow['drop_rate']}")
    with open(out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"\n[serve_qos_bench] wrote {out} ({len(data['models'])} "
          f"model(s), batch {batch}, loads {list(load_factors)})")
    return data


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="AlexNet only, small batch (CI bench-smoke)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--frames", type=int, default=None)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0,
                    help="params/calibration/stream/schedule RNG seed")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="interactive-class deadline (default: derived "
                         "from the measured service time)")
    ap.add_argument("--traffic-mix", default=None, dest="traffic_mix",
                    help="name:priority:share[:deadline_ms],... "
                         "(default: interactive 25%% + batch 75%%)")
    ap.add_argument("--load", type=float, action="append", default=None,
                    dest="load_factors",
                    help="arrival rate as a fraction of measured steady "
                         "throughput (repeatable; default 0.6 1.2)")
    ap.add_argument("--place-stages", action="store_true",
                    help="pin stage i to jax.devices()[i %% n]")
    ap.add_argument("--poisson", action="store_true",
                    help="exponential inter-arrival gaps (bursty)")
    ap.add_argument("--no-admission", action="store_true",
                    help="disable estimated-wait admission control "
                         "(PR-4 lane-bound-only admission)")
    ap.add_argument("--flush-guard-ms", type=float, default=None,
                    help="fixed expedited-flush guard (default: "
                         "adaptive, 25%% of the service estimate + 2ms)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--model", action="append", default=None,
                    choices=sorted(W.CNN_MODELS), dest="models")
    args = ap.parse_args(argv)
    from benchmarks.run import print_csv
    csv: list[str] = []

    def emit(name, us, derived=""):
        csv.append(f"{name},{us:.1f},{derived}")

    run(emit, quick=args.quick, batch=args.batch, frames=args.frames,
        out=args.out, models=args.models, stages=args.stages,
        seed=args.seed, slo_ms=args.slo_ms,
        traffic_mix_spec=args.traffic_mix,
        load_factors=tuple(args.load_factors or DEFAULT_LOAD_FACTORS),
        place_stages=args.place_stages, poisson=args.poisson,
        admission_control=not args.no_admission,
        flush_guard_ms=args.flush_guard_ms)
    print_csv(csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
