"""QPS-knee benchmark: the headline capacity number per model.

``serve_qos_bench`` reports QoS behaviour at load factors *relative to*
the measured steady throughput; this bench answers the absolute
question — how many requests per second can a deployment take while the
interactive class holds its SLO? For each model it compiles one
:class:`EngineProgram`, measures steady pipeline throughput, then runs
the bracketing absolute-QPS sweep (``repro.launch.serve_cnn.serve_knee``:
double the arrival rate while the deadline-armed classes miss less than
``--miss-target`` of the time, then bisect the sustained/unsustained
bracket). The knee — max sustained QPS — lands in
``BENCH_serve_knee.json`` with every probe recorded, the control-plane
config (admission, flush guard, estimator warm start), and the seed
that replays the exact schedule. Built, schema-validated, gated against
``benchmarks/baselines/`` and uploaded by the CI bench-smoke job.

Two extensions ride on the same sweep:

* ``--arrival poisson`` additionally benches the knee under Poisson
  (exponential inter-arrival) traffic and records it as a
  ``<model>:poisson`` row alongside the uniform knee — burstiness costs
  capacity, and the artifact shows how much;
* ``--replicas-sweep 1,2,4`` runs the knee-vs-R scaling sweep through a
  routed :class:`repro.serving.ReplicaPool` (R>1 brackets open at the
  R=1 knee, so "replication never loses to one replica" is probed
  directly) and records a ``knee_scaling`` block per model —
  schema-validated and gated (``knee_r2 / knee_r1 >= 1``) in CI under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``;
* ``--rescale`` (default on; ``--no-rescale`` skips) drives a load ramp
  across the R=1 knee with an ``ElasticController`` watching the
  frontend: when the armed miss rate crosses the target, the controller
  live-rescales the fleet (drain -> swap -> resume, no request dropped:
  ``hung == 0`` is a hard CI gate) and the post-rescale knee is
  re-bracketed on the same server — recorded as a ``knee_after_rescale``
  block per model.

  PYTHONPATH=src:. python benchmarks/serve_knee_bench.py --quick \
      --arrival poisson --replicas-sweep 1,2,4                   # CI
  PYTHONPATH=src:. python benchmarks/serve_knee_bench.py          # full
"""

from __future__ import annotations

import argparse
import json
import platform
import time

import jax

from repro.core import workload as W
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_cnn import (compile_for_serving, serve_knee,
                                    serve_knee_rescale)
from repro.serving import parse_traffic_mix

SCHEMA_VERSION = 1
DEFAULT_OUT = "BENCH_serve_knee.json"
DEFAULT_MISS_TARGET = 0.01


def bench_model(model: str, *, batch: int, frames: int | None,
                stages: int, seed: int, slo_ms: float | None,
                traffic_mix, miss_target: float, refine_iters: int,
                max_factor: float, flush_guard_ms: float | None,
                admission_control: bool, place_stages: bool,
                poisson: bool, program=None, replicas: int = 1,
                replica_mode: str = "pipeline",
                start_qps: float | None = None) -> dict:
    """One model: throughput phase + the bracketing QPS sweep, over one
    compiled program (pass ``program`` to reuse it across the arrival
    and replica variants)."""
    if program is None:
        program = compile_for_serving(model, bits=8, seed=seed)
    n = frames if frames is not None else (6 + 2 * stages) * batch
    return serve_knee(model, frames=n, batch=batch, stages=stages,
                      seed=seed, slo_ms=slo_ms, traffic_mix=traffic_mix,
                      miss_target=miss_target, refine_iters=refine_iters,
                      max_factor=max_factor, start_qps=start_qps,
                      flush_guard_ms=flush_guard_ms,
                      admission_control=admission_control,
                      place_stages=place_stages, poisson=poisson,
                      replicas=replicas, replica_mode=replica_mode,
                      program=program, verbose=True)


def run(emit, *, quick: bool = False, batch: int | None = None,
        frames: int | None = None, out: str = DEFAULT_OUT,
        models: list[str] | None = None, stages: int = 2,
        seed: int = 0, slo_ms: float | None = None,
        traffic_mix_spec: str | None = None,
        miss_target: float = DEFAULT_MISS_TARGET,
        refine_iters: int | None = None, max_factor: float = 8.0,
        flush_guard_ms: float | None = None,
        admission_control: bool = True,
        place_stages: bool = False, poisson: bool = False,
        arrival: str = "uniform", replicas: int = 1,
        replica_mode: str = "pipeline",
        replicas_sweep: list[int] | None = None,
        rescale: bool = True) -> dict:
    if arrival not in ("uniform", "poisson"):
        raise ValueError(f"unknown arrival {arrival!r}")
    if models is None:
        models = ["alexnet"] if quick else list(W.CNN_MODELS)
    if batch is None:
        batch = 8 if quick else 32
    if refine_iters is None:
        refine_iters = 2 if quick else 3
    if replicas_sweep is not None:
        replicas_sweep = sorted({int(r) for r in replicas_sweep})
        if any(r < 1 for r in replicas_sweep):
            raise ValueError(f"replicas_sweep={replicas_sweep} has R < 1")
        if 1 not in replicas_sweep:
            raise ValueError("replicas_sweep needs the R=1 baseline "
                             "(knee_vs_r1 is a ratio against it)")
    mix = (parse_traffic_mix(traffic_mix_spec, slo_ms)
           if traffic_mix_spec else None)
    data: dict = {
        "schema_version": SCHEMA_VERSION,
        "bench": "serve_knee",
        "quick": quick,
        "batch": batch,
        "frames": frames,          # null = per-model default
        "stages": stages,
        "seed": seed,              # replays params, calibration, frames
        "slo_ms": slo_ms,          # and every probe's arrival schedule
        "poisson": poisson,
        "arrival": arrival,
        "replicas": replicas,
        "replica_mode": replica_mode,
        "replicas_sweep": replicas_sweep,
        "rescale": rescale,
        "device_count": jax.device_count(),
        "miss_target": miss_target,
        "max_factor": max_factor,
        "refine_iters": refine_iters,
        "admission_control": admission_control,
        "flush_guard_ms": flush_guard_ms,
        "place_stages": place_stages,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_version": jax.__version__,
        "backend": jax.devices()[0].platform,
        "host": platform.machine(),
        "models": {},
    }
    common = dict(batch=batch, frames=frames, stages=stages, seed=seed,
                  slo_ms=slo_ms, traffic_mix=mix, miss_target=miss_target,
                  refine_iters=refine_iters, max_factor=max_factor,
                  flush_guard_ms=flush_guard_ms,
                  admission_control=admission_control,
                  place_stages=place_stages)
    base_poisson = poisson     # legacy flag: the base sweep is bursty
    for model in models:
        prog = compile_for_serving(model, bits=8, seed=seed)
        row = bench_model(model, poisson=base_poisson, program=prog,
                          replicas=replicas, replica_mode=replica_mode,
                          **common)
        data["models"][model] = row
        emit(f"serve_knee/{model}/knee_qps", 0.0,
             f"{row['knee_qps']}qps|x{row['knee_of_steady']}_of_steady|"
             f"miss={row['knee_miss_rate']}|"
             f"probes={len(row['probes'])}")
        # Variant rows (bursty arrival, R>1 replicas) hold the base
        # row's *resolved* SLO constant: re-deriving per variant would
        # tighten the budget as fleet steady grows with R (per-batch
        # traversal latency does not shrink), so each row would measure
        # a different contract and the knee ratios would be meaningless.
        pinned = dict(common)
        if pinned["slo_ms"] is None:
            pinned["slo_ms"] = row["slo_ms"]
        if arrival == "poisson" and not base_poisson:
            # Bursty variant of the same sweep: exponential inter-arrival
            # gaps from the same seed, recorded alongside the uniform
            # knee so the burstiness cost is visible in the artifact.
            prow = bench_model(model, poisson=True, program=prog,
                               replicas=replicas,
                               replica_mode=replica_mode, **pinned)
            data["models"][f"{model}:poisson"] = prow
            emit(f"serve_knee/{model}:poisson/knee_qps", 0.0,
                 f"{prow['knee_qps']}qps|x{prow['knee_of_steady']}"
                 f"_of_steady|probes={len(prow['probes'])}")
        if replicas_sweep:
            base = (row if replicas == 1
                    else bench_model(model, poisson=base_poisson,
                                     program=prog, replicas=1, **pinned))
            knee_r1 = base["knee_qps"]
            # copy: base may be the model row itself, which grows the
            # knee_scaling block below — a cycle json.dump would reject
            rows = {"1": dict(base)}
            for r in replicas_sweep:
                if r == 1:
                    continue
                # Open each R>1 bracket at the R=1 knee: if R replicas
                # sustain the rate one replica topped out at, the knee
                # ratio is >= 1 by construction of "max sustained".
                rows[str(r)] = bench_model(
                    model, poisson=base_poisson, program=prog,
                    replicas=r, replica_mode=replica_mode,
                    start_qps=knee_r1, **pinned)
            # A row with no sustained probe has knee_qps None — keep the
            # ratio None too (the CI gate then fails on the missing
            # number, which is the intended signal) instead of crashing.
            ratios = {str(r): (None if knee_r1 is None
                               or rows[str(r)]["knee_qps"] is None
                               else round(rows[str(r)]["knee_qps"]
                                          / knee_r1, 4))
                      for r in replicas_sweep if r != 1}
            data["models"][model]["knee_scaling"] = {
                "device_count": jax.device_count(),
                "mode": replica_mode,
                "rows": rows,
                "knee_vs_r1": ratios,
            }
            emit(f"serve_knee/{model}/knee_scaling", 0.0,
                 "|".join(f"r{r}={rows[str(r)]['knee_qps']}qps"
                          + ("" if r == 1
                             else f"(x{ratios[str(r)]})")
                          for r in replicas_sweep))
        if rescale:
            # Elastic-runtime row: ramp across the R=1 knee with the
            # controller live, measure the drain-swap-resume rescale
            # under load, then re-bracket the knee on the rescaled
            # server. The ramp opens at the measured R=1 knee so the
            # very first segment crosses it.
            n = frames if frames is not None else (6 + 2 * stages) * batch
            rrow = serve_knee_rescale(
                model, frames=n, batch=batch, stages=stages, seed=seed,
                slo_ms=pinned["slo_ms"], traffic_mix=mix,
                miss_target=miss_target, start_qps=row["knee_qps"],
                max_factor=max_factor, refine_iters=refine_iters,
                flush_guard_ms=flush_guard_ms,
                admission_control=admission_control,
                place_stages=place_stages,
                scenario="poisson" if base_poisson else None,
                replica_mode=replica_mode, program=prog, verbose=True)
            data["models"][model]["knee_after_rescale"] = rrow
            emit(f"serve_knee/{model}/knee_after_rescale", 0.0,
                 f"rescales={rrow['n_rescales']}"
                 + ("(forced)" if rrow["forced"] else "")
                 + f"|R{rrow['replicas_before']}->"
                 f"{rrow['replicas_after']}|hung={rrow['hung']}|"
                 f"miss {rrow['armed_miss_at_trigger']}->"
                 f"{rrow['armed_miss_after_rescale']}|"
                 f"knee={rrow['knee']['knee_qps']}qps")
    with open(out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"\n[serve_knee_bench] wrote {out} ({len(data['models'])} "
          f"model(s), batch {batch}, miss target {miss_target:.0%})")
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
    ap.add_argument("--miss-target", type=float,
                    default=DEFAULT_MISS_TARGET,
                    help="armed-class miss rate defining 'sustained' "
                         "(default 0.01)")
    ap.add_argument("--max-factor", type=float, default=8.0,
                    help="sweep cap as a multiple of measured steady "
                         "fps (default 8)")
    ap.add_argument("--refine-iters", type=int, default=None,
                    help="bisection refinements of the bracket "
                         "(default 3, 2 with --quick)")
    ap.add_argument("--flush-guard-ms", type=float, default=None,
                    help="fixed flush guard (default: adaptive)")
    ap.add_argument("--no-admission", action="store_true",
                    help="disable estimated-wait admission control")
    ap.add_argument("--place-stages", action="store_true",
                    help="pin stage i to jax.devices()[i %% n]")
    ap.add_argument("--poisson", action="store_true",
                    help="exponential inter-arrival gaps (bursty); "
                         "same as --arrival poisson")
    ap.add_argument("--arrival", default="uniform",
                    choices=("uniform", "poisson"),
                    help="'poisson' additionally records a "
                         "<model>:poisson row beside the uniform knee")
    ap.add_argument("--replicas", type=int, default=1,
                    help="pipeline replicas behind the least-wait "
                         "router (default 1 = plain PipelineExecutor)")
    ap.add_argument("--replica-mode", default="pipeline",
                    choices=("pipeline", "stage-shard"),
                    dest="replica_mode",
                    help="replica placement: whole pipeline per device "
                         "or stages across a contiguous device slice")
    ap.add_argument("--replicas-sweep", default=None,
                    dest="replicas_sweep",
                    help="comma list, e.g. 1,2,4: knee-vs-R scaling "
                         "sweep (R>1 brackets open at the R=1 knee); "
                         "records a knee_scaling block per model")
    ap.add_argument("--rescale", dest="rescale", action="store_true",
                    default=True,
                    help="elastic-runtime ramp: live rescale across the "
                         "knee, records knee_after_rescale (default on)")
    ap.add_argument("--no-rescale", dest="rescale", action="store_false",
                    help="skip the elastic-runtime rescale ramp")
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
        miss_target=args.miss_target, refine_iters=args.refine_iters,
        max_factor=args.max_factor, flush_guard_ms=args.flush_guard_ms,
        admission_control=not args.no_admission,
        place_stages=args.place_stages, poisson=args.poisson,
        arrival=args.arrival, replicas=args.replicas,
        replica_mode=args.replica_mode,
        replicas_sweep=([int(r) for r in args.replicas_sweep.split(",")]
                        if args.replicas_sweep else None),
        rescale=args.rescale)
    print_csv(csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
