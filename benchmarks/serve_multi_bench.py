"""Multi-tenant serving benchmark: the model zoo behind one frontend.

The single-model benches answer "how much traffic can a deployment of
model M take"; this bench answers the production question the registry
exists for — N compiled models served *concurrently* through one
frontend with per-tenant ``(model, priority)`` lanes and weighted
round-robin fairness (:mod:`repro.serving.server`). Three blocks land in
``BENCH_serve_multi.json``:

* ``models`` — per tenant: calibrated steady fps, modeled Alg-1 fps,
  its share of the arrival mix, its derived SLO, and its armed miss
  rate at the aggregate knee;
* ``aggregate`` — the bracketing QPS sweep over the *combined* arrival
  stream (each probe splits the aggregate rate across tenants by share,
  draws one seeded schedule per tenant, tags and merge-sorts them into
  one interleaved stream): the knee is the max aggregate rate at which
  **every** tenant's interactive class holds its SLO, recorded against
  the harmonic aggregate capacity
  ``1 / sum(share_t / steady_t)`` (serving one mixed frame costs the
  share-weighted sum of per-tenant batch times on shared silicon);
* ``isolation`` — the headline fairness number, gated in CI: flood one
  tenant at 3x its own calibrated capacity while every other tenant
  trickles deadline-armed traffic at a sustainable 0.3x, and record the
  worst victim's armed miss rate. Per-tenant lanes + WRR + own-tenant
  admission pricing must keep that under the miss target — a flooded
  neighbour is the flooded tenant's problem.

  PYTHONPATH=src:. python benchmarks/serve_multi_bench.py --quick  # CI
  PYTHONPATH=src:. python benchmarks/serve_multi_bench.py          # full
"""

from __future__ import annotations

import argparse
import json
import platform
import time

import jax

from repro.core import workload as W
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (ProgramRegistry, ServerConfig, TrafficClass,
                           build_server, make_schedule, merge_schedules,
                           replay, tag_tenant)
from repro.serving.server import synthetic_stream

SCHEMA_VERSION = 1
DEFAULT_OUT = "BENCH_serve_multi.json"
DEFAULT_MISS_TARGET = 0.05
QUICK_MODELS = ["alexnet", "zf"]

# Derived per-tenant SLO: (K + 3) batch windows at the tenant's *solo*
# steady rate — the single-model convention — stretched by this factor
# because N tenants share the host's cores, so every tenant's effective
# window under concurrent load is wider than its solo calibration.
SLO_SCALE = 2.0


def _tenant_mix(name: str, slo_ms: float) -> tuple[TrafficClass, ...]:
    """Each tenant's 25/75 interactive/batch mix under tenant-scoped
    class names, so per-(tenant, class) outcomes stay separable in the
    shared FrontendStats."""
    return (TrafficClass(f"{name}:interactive", priority=1,
                         deadline_ms=slo_ms, share=0.25),
            TrafficClass(f"{name}:batch", priority=0, deadline_ms=None,
                         share=0.75))


def _armed_outcomes(stats, name: str) -> dict:
    """One tenant's interactive-class outcome row from a replay."""
    cs = stats.classes.get(f"{name}:interactive")
    if cs is None:
        return {"armed_submitted": 0, "armed_missed": 0,
                "armed_miss_rate": 0.0}
    missed = cs.expired + cs.rejected + cs.rejected_wait + cs.late
    return {
        "armed_submitted": cs.submitted,
        "armed_missed": missed,
        "armed_miss_rate": round(missed / cs.submitted, 4)
        if cs.submitted else 0.0,
    }


def run(emit, *, quick: bool = False, batch: int | None = None,
        frames: int | None = None, out: str = DEFAULT_OUT,
        models: list[str] | None = None, stages: int = 2,
        seed: int = 0, miss_target: float = DEFAULT_MISS_TARGET,
        refine_iters: int | None = None, max_factor: float = 4.0,
        flood_factor: float = 3.0, victim_factor: float = 0.3,
        verbose: bool = True) -> dict:
    if models is None:
        models = QUICK_MODELS if quick else list(W.CNN_MODELS)
    if len(models) < 2:
        raise ValueError(f"multi-tenant bench needs >= 2 models, got "
                         f"{models}")
    if batch is None:
        batch = 8 if quick else 16
    if refine_iters is None:
        refine_iters = 2 if quick else 3
    if not 0.0 < miss_target < 1.0:
        raise ValueError(f"miss_target={miss_target} not in (0, 1)")
    n_frames = frames if frames is not None else (6 + 2 * stages) * batch
    share = 1.0 / len(models)             # equal tenant shares

    registry = ProgramRegistry.compile(models, bits=8, seed=seed)
    streams = {m: synthetic_stream(m, n_frames, seed) for m in models}
    cfg = ServerConfig(batch=batch, stages=stages, seed=seed,
                       calib_frames=n_frames)
    srv = build_server(registry, cfg, streams=streams, verbose=verbose)
    try:
        steady = {m: srv.runtime(m).steady_fps for m in models}
        slo = {m: round(SLO_SCALE * (stages + 3) * 1e3 * batch
                        / max(steady[m], 1e-9), 1) for m in models}
        # Harmonic aggregate capacity: a share-weighted mixed frame
        # costs sum(share/steady_t) seconds of engine time.
        agg_steady = 1.0 / sum(share / max(steady[m], 1e-9)
                               for m in models)

        def _replay(rates: dict[str, float]) -> tuple:
            """One merged multi-tenant replay at per-tenant rates;
            returns (frontend stats, per-tenant armed outcome rows)."""
            fe = srv.open_frontend(dict(rates))
            scheds = [tag_tenant(
                make_schedule(len(streams[m]), rates[m],
                              _tenant_mix(m, slo[m]), seed=seed + i), m)
                for i, m in enumerate(models)]
            replay(fe, streams, merge_schedules(*scheds))
            fe.close()
            st = fe.stats_snapshot()
            return st, {m: _armed_outcomes(st, m) for m in models}

        def _probe(agg_rate: float) -> dict:
            st, per_tenant = _replay({m: share * agg_rate
                                      for m in models})
            worst = max(r["armed_miss_rate"] for r in per_tenant.values())
            row = {
                "arrival_fps": round(agg_rate, 3),
                "sustained": bool(worst < miss_target),
                "worst_armed_miss_rate": worst,
                "client_fps": round(st.fps, 3),
                "submitted": st.submitted,
                "completed": st.completed,
                "expired": st.expired,
                "rejected": st.rejected,
                "rejected_wait": st.rejected_wait,
                "failed": st.failed,
                "per_tenant": per_tenant,
            }
            if verbose:
                print(f"[serve_multi] probe {agg_rate:8.2f} qps agg: "
                      f"worst armed miss {worst:6.2%} "
                      f"({'sustained' if row['sustained'] else 'MISS'})")
            return row

        # Aggregate knee: bracket by doubling from 0.5x the harmonic
        # capacity while every tenant sustains, then bisect.
        probes: list[dict] = []
        cap = max_factor * agg_steady
        lo_rate, lo_row, hi_rate = None, None, None
        rate = 0.5 * agg_steady
        while hi_rate is None:
            row = _probe(rate)
            probes.append(row)
            if row["sustained"]:
                lo_rate, lo_row = rate, row
                if rate >= cap:
                    break
                rate = min(2 * rate, cap)
            else:
                hi_rate = rate
        if lo_rate is None:
            floor = 0.05 * agg_steady
            while lo_rate is None and rate / 2 >= floor:
                rate = rate / 2
                row = _probe(rate)
                probes.append(row)
                if row["sustained"]:
                    lo_rate, lo_row = rate, row
                else:
                    hi_rate = rate
        for _ in range(max(0, int(refine_iters))):
            if lo_rate is None or hi_rate is None or \
                    hi_rate / lo_rate < 1.05:
                break
            mid = (lo_rate + hi_rate) / 2
            row = _probe(mid)
            probes.append(row)
            if row["sustained"]:
                lo_rate, lo_row = mid, row
            else:
                hi_rate = mid

        # Isolation: flood tenant 0 at flood_factor x its own solo
        # capacity (armed mix included — the flood tenant's own misses
        # are expected and recorded); every other tenant trickles at a
        # sustainable victim_factor x. The gated headline is the worst
        # *victim* armed miss rate.
        flood_tenant = models[0]
        iso_rates = {m: (flood_factor * steady[m] if m == flood_tenant
                         else victim_factor * steady[m]) for m in models}
        _, iso = _replay(iso_rates)
        victims = {m: dict(iso[m], arrival_fps=round(iso_rates[m], 3))
                   for m in models if m != flood_tenant}
        victim_miss = max(r["armed_miss_rate"] for r in victims.values())

        data: dict = {
            "schema_version": SCHEMA_VERSION,
            "bench": "serve_multi",
            "quick": quick,
            "batch": batch,
            "frames": n_frames,
            "stages": stages,
            "seed": seed,              # replays every tenant's schedule
            "miss_target": miss_target,
            "slo_scale": SLO_SCALE,
            "max_factor": max_factor,
            "refine_iters": refine_iters,
            "tenant_share": round(share, 4),
            "device_count": jax.device_count(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "jax_version": jax.__version__,
            "backend": jax.devices()[0].platform,
            "host": platform.machine(),
            "models": {},
            "aggregate": {
                "agg_steady_fps": round(agg_steady, 3),
                "knee_qps": None if lo_rate is None else round(lo_rate, 3),
                "knee_of_agg_steady": (
                    None if lo_rate is None
                    else round(lo_rate / max(agg_steady, 1e-9), 4)),
                "knee_worst_armed_miss_rate": (
                    None if lo_row is None
                    else lo_row["worst_armed_miss_rate"]),
                "bracket_unsustained_qps": (
                    None if hi_rate is None else round(hi_rate, 3)),
                "probes": probes,
            },
            "isolation": {
                "flood_tenant": flood_tenant,
                "flood_factor": flood_factor,
                "victim_factor": victim_factor,
                "flood_armed_miss_rate": iso[flood_tenant]
                ["armed_miss_rate"],
                "victim_armed_miss_rate": victim_miss,
                "victims": victims,
            },
        }
        for m in models:
            rt = srv.runtime(m)
            data["models"][m] = {
                "steady_fps": round(steady[m], 3),
                "modeled_fps_alg1": round(rt.program.fps(), 3),
                "lat1_ms": (None if rt.lat1_s is None
                            else round(rt.lat1_s * 1e3, 3)),
                "share": round(share, 4),
                "slo_ms": slo[m],
                "knee": (None if lo_row is None
                         else dict(lo_row["per_tenant"][m],
                                   arrival_fps=round(share * lo_rate, 3))),
            }
            emit(f"serve_multi/{m}/steady_fps", 0.0,
                 f"{data['models'][m]['steady_fps']}fps|"
                 f"slo={slo[m]}ms")
    finally:
        srv.close()

    agg = data["aggregate"]
    emit("serve_multi/aggregate/knee_qps", 0.0,
         f"{agg['knee_qps']}qps|x{agg['knee_of_agg_steady']}_of_agg|"
         f"probes={len(agg['probes'])}")
    emit("serve_multi/isolation/victim_armed_miss_rate", 0.0,
         f"{victim_miss}|flood={flood_tenant}@{flood_factor}x")
    with open(out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"\n[serve_multi_bench] wrote {out} ({len(models)} tenants, "
          f"batch {batch}, agg knee "
          f"{agg['knee_qps']} qps, victim miss {victim_miss:.2%} "
          f"vs target {miss_target:.0%})")
    return data


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="two tenants (alexnet + zf), small batch "
                         "(CI bench-smoke)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--frames", type=int, default=None,
                    help="frames per tenant per probe (default: "
                         "(6 + 2*stages) * batch)")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0,
                    help="params/calibration/stream/schedule RNG seed")
    ap.add_argument("--miss-target", type=float,
                    default=DEFAULT_MISS_TARGET,
                    help="armed-class miss rate defining 'sustained' "
                         "and the isolation gate (default 0.05)")
    ap.add_argument("--max-factor", type=float, default=4.0,
                    help="sweep cap as a multiple of the harmonic "
                         "aggregate capacity (default 4)")
    ap.add_argument("--refine-iters", type=int, default=None,
                    help="bisection refinements (default 3, 2 quick)")
    ap.add_argument("--flood-factor", type=float, default=3.0,
                    help="isolation flood rate as a multiple of the "
                         "flooded tenant's solo steady fps (default 3)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--model", action="append", default=None,
                    choices=sorted(W.CNN_MODELS), dest="models",
                    help="repeatable; >= 2 required (default: "
                         "alexnet+zf quick, all four full)")
    args = ap.parse_args(argv)
    from benchmarks.run import print_csv
    csv: list[str] = []

    def emit(name, us, derived=""):
        csv.append(f"{name},{us:.1f},{derived}")

    run(emit, quick=args.quick, batch=args.batch, frames=args.frames,
        out=args.out, models=args.models, stages=args.stages,
        seed=args.seed, miss_target=args.miss_target,
        refine_iters=args.refine_iters, max_factor=args.max_factor,
        flood_factor=args.flood_factor)
    print_csv(csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
