"""Chaos benchmark: adversarial traffic + fault injection, gated on
liveness.

Every other serving bench measures the healthy path. This one measures
the contract that makes those numbers trustworthy — *every submitted
request resolves, never hangs* — while the deployment is actively being
hurt. Two parts per model, over one compiled
:class:`~repro.core.program.EngineProgram`:

* **Adversarial-arrival knees** — the same bracketing absolute-QPS
  sweep as ``serve_knee_bench``, but driven by the hostile arrival
  processes in :data:`repro.serving.SCENARIOS` (on/off flash crowds,
  lognormal and Pareto heavy-tail gaps, diurnal ramps) beside the
  uniform baseline, so the capacity cost of burstiness is a recorded
  number (``knee_of_steady`` per scenario) rather than folklore.

* **Fault replays** — a two-replica routed :class:`ReplicaPool` whose
  first replica is wrapped in a :class:`~repro.serving.ChaosExecutor`,
  calibrated healthy, then armed with one :class:`FaultPlan` per
  scenario: ``kill_replica`` (dies mid-batch, recovers later — probes
  re-admit it), ``straggler`` (every delivery dragged ``slowdown_s``
  late, the router must steer by price), ``fail_at_t`` (drops off the
  bus at time T, permanently). Each replay records the liveness
  headline (``hung``, ``resolved_frac``), the chaos-tier armed miss
  rate (failed counts against the SLO), the achieved pacing, and the
  :func:`~repro.serving.recovery_report` time-to-recover.

FPGA correspondence (DESIGN.md §9): a replica kill is a PE/stage hard
fault — the paper's fabric has no ECC, the batch in the array is lost;
a flash crowd is an input-buffer overrun at the host interface; a
straggler is a clock-degraded or thermally-throttled region; and
``fail_at_t`` is a board dropping off the host bus mid-run.

Results land in ``BENCH_serve_chaos.json`` — schema-validated, gated
against ``benchmarks/baselines/serve_chaos.json`` (hung == 0 and
resolved_frac == 1.0 are *hard* gates; recovery time and scenario knees
are warn-only bands) and uploaded by the CI bench-smoke job.

  PYTHONPATH=src:. python benchmarks/serve_chaos_bench.py --quick   # CI
  PYTHONPATH=src:. python benchmarks/serve_chaos_bench.py           # full
"""

from __future__ import annotations

import argparse
import json
import platform
import time

import jax
import numpy as np

from repro.core import workload as W
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_cnn import compile_for_serving, serve_knee
from repro.serving import (ChaosExecutor, FaultPlan, PipelineExecutor,
                           ReplicaPool, armed_class_names, default_mix,
                           make_scenario_schedule, pacing_report,
                           pipeline_throughput, recovery_report, replay,
                           synthetic_stream, warmed_frontend)

SCHEMA_VERSION = 1
DEFAULT_OUT = "BENCH_serve_chaos.json"
# Chaos verdicts use a looser band than the healthy knee's 1%: burst
# scenarios are *supposed* to miss during the burst — the question is
# whether the deployment recovers, not whether it is unconditionally
# clean.
DEFAULT_MISS_TARGET = 0.05
DEFAULT_LOAD_FACTOR = 0.5
FAULTS = ("kill_replica", "straggler", "fail_at_t")
ADVERSARIAL_SCENARIOS = ("onoff", "lognormal", "pareto", "diurnal")
QUICK_SCENARIOS = ("onoff", "pareto")


def _fault_plan(fault: str, *, batch: int, steady: float, n: int,
                rate: float) -> FaultPlan:
    """One replica's fault program, scaled to the replay: offsets are in
    the *victim's* dispatched batches (it sees roughly half the
    ``n / batch`` total), so the fault lands early enough that the
    post-fault window dominates the artifact."""
    window = batch / max(steady, 1e-9)
    victim_batches = max(4, n // (2 * batch))
    if fault == "kill_replica":
        # Dead for ~a third of its share, then answers probes again —
        # quarantine, steering, and re-admission all get exercised.
        return FaultPlan(kill_at_batch=3,
                         recover_at_batch=3 + max(3, victim_batches // 3))
    if fault == "straggler":
        # Every delivery dragged ~3 batch windows late: far past the
        # router's 3x-median straggler band, without ever failing.
        return FaultPlan(straggle_at_batch=3,
                         slowdown_s=round(3 * window, 6))
    if fault == "fail_at_t":
        # Board drops off the bus a quarter into the replay, for good.
        return FaultPlan(fail_after_s=round(0.25 * n / max(rate, 1e-9), 6))
    raise ValueError(f"unknown fault {fault!r} (expected one of {FAULTS})")


def bench_fault(model: str, prog, fault: str, *, batch: int, stages: int,
                frames: int, seed: int, slo_ms: float,
                miss_target: float, load_factor: float,
                flush_guard_ms: float | None, admission_control: bool,
                verbose: bool = True) -> dict:
    """One fault replay: build a 2-replica pool with the victim behind a
    benign ChaosExecutor, calibrate healthy through the pool, arm the
    plan, replay a seeded uniform stream at ``load_factor * fleet
    steady`` open-loop, and report liveness + recovery."""
    reps = [PipelineExecutor(prog, stages=stages, batch_size=batch,
                             output="top1") for _ in range(2)]
    victim = ChaosExecutor(reps[0], FaultPlan(), name=f"{model}-victim")
    pool = ReplicaPool(prog, executors=[victim, reps[1]],
                       router_seed=seed, probe_every=4)
    pool.start()
    stream = synthetic_stream(model, frames, seed)
    try:
        warmup_s, lat1_s, calib = pipeline_throughput(pool, stream, batch)
        steady = calib.steady_fps
        rate = load_factor * steady
        plan = _fault_plan(fault, batch=batch, steady=steady, n=frames,
                           rate=rate)
        mix = default_mix(slo_ms)
        armed = armed_class_names(mix)
        schedule, _ = make_scenario_schedule("uniform", frames, rate, mix,
                                             seed=seed)
        pool.reset_stats()
        fe = warmed_frontend(pool, steady, rate, batch, max_wait_ms=None,
                             admission_control=admission_control,
                             flush_guard_ms=flush_guard_ms, lat1_s=lat1_s,
                             max_queue=max(256, 2 * frames))
        victim.arm(plan)
        reqs = replay(fe, stream, schedule, raise_failed=False)
        pacing = pacing_report(schedule, reqs)
        fe.close()
        st = fe.stats
    finally:
        pool.close()

    # Chaos-tier armed miss: dropped, refused, late — or *failed*. The
    # healthy knee excludes failures (there, a failure is a bench bug);
    # a fault window must count them against the SLO.
    armed_reqs = [r for r in reqs if r.deadline_s is not None]
    armed_missed = sum(1 for r in armed_reqs
                       if r.missed_deadline()
                       or r.outcome in ("failed", "rejected"))
    cls = [st.klass(c) for c in armed if c in st.classes]
    total_s = [s for c in cls for s in c.total_s]
    p99_ms = (round(float(np.percentile(np.asarray(total_s), 99)) * 1e3, 3)
              if total_s else None)
    # ~4 full-batch assembly windows per bucket: enough armed arrivals
    # (25% of the mix) that one straggling request cannot flip a
    # window's verdict.
    window_s = 4 * batch / max(rate, 1e-9)
    recovery = recovery_report(reqs, fault_t0=victim.t_first_fault,
                               window_s=window_s, miss_target=miss_target)
    row = {
        "fault": fault,
        "plan": plan.to_json(),
        "replicas": pool.n_replicas,
        "frames": frames,
        "batch": batch,
        "slo_ms": slo_ms,
        "miss_target": miss_target,
        "load_factor": load_factor,
        "fleet_steady_fps": round(steady, 3),
        "unloaded_lat1_ms": round(lat1_s * 1e3, 3),
        "compile_plus_warmup_s": round(warmup_s, 3),
        "arrival_fps": round(rate, 3),
        "submitted": st.submitted,
        "completed": st.completed,
        "failed": st.failed,
        "expired": st.expired,
        "rejected": st.rejected,
        "rejected_wait": st.rejected_wait,
        "resolved": st.resolved,
        "hung": st.hung,
        "resolved_frac": (round(st.resolved / st.submitted, 6)
                          if st.submitted else None),
        "armed_submitted": len(armed_reqs),
        "armed_missed": armed_missed,
        "armed_miss_rate": (round(armed_missed / len(armed_reqs), 4)
                            if armed_reqs else None),
        "armed_p99_ms": p99_ms,
        "injected_failures": victim.injected_failures,
        "injected_slowdowns": victim.injected_slowdowns,
        "pacing": pacing,
        "recovery": recovery,
        "router": pool.router.snapshot(),
        "replica_rows": pool.replica_rows(),
    }
    if verbose:
        rec = recovery["recovered_s"]
        print(f"[serve_chaos] {model} fault={fault}: "
              f"{st.resolved}/{st.submitted} resolved, hung {st.hung}, "
              f"failed {st.failed}, injected "
              f"{victim.injected_failures}+{victim.injected_slowdowns}slow"
              f" | recovered "
              + (f"{rec:.3f}s" if rec is not None else "n/a"))
    return row


def run(emit, *, quick: bool = False, batch: int | None = None,
        frames: int | None = None, out: str = DEFAULT_OUT,
        models: list[str] | None = None, stages: int = 2,
        seed: int = 0, slo_ms: float | None = None,
        miss_target: float = DEFAULT_MISS_TARGET,
        refine_iters: int | None = None, max_factor: float = 8.0,
        load_factor: float = DEFAULT_LOAD_FACTOR,
        flush_guard_ms: float | None = None,
        admission_control: bool = True,
        scenarios: list[str] | None = None,
        faults: list[str] | None = None) -> dict:
    if models is None:
        models = ["alexnet"] if quick else list(W.CNN_MODELS)
    if batch is None:
        batch = 8 if quick else 32
    if refine_iters is None:
        refine_iters = 1 if quick else 3
    if scenarios is None:
        scenarios = list(QUICK_SCENARIOS if quick
                         else ADVERSARIAL_SCENARIOS)
    bad = [s for s in scenarios if s not in ADVERSARIAL_SCENARIOS]
    if bad:
        raise ValueError(f"unknown scenario(s) {bad} "
                         f"(expected from {ADVERSARIAL_SCENARIOS})")
    if faults is None:
        faults = list(FAULTS)
    bad = [f for f in faults if f not in FAULTS]
    if bad:
        raise ValueError(f"unknown fault(s) {bad} (expected from {FAULTS})")
    if not 0.0 < load_factor < 1.0:
        raise ValueError(f"load_factor={load_factor} not in (0, 1): the "
                         f"fault replays must leave headroom for the "
                         f"survivor to absorb the victim's share")
    knee_frames = frames if frames is not None else (6 + 2 * stages) * batch
    chaos_frames = frames if frames is not None \
        else (12 + 2 * stages) * batch
    data: dict = {
        "schema_version": SCHEMA_VERSION,
        "bench": "serve_chaos",
        "quick": quick,
        "batch": batch,
        "frames": frames,          # null = per-part default
        "stages": stages,
        "seed": seed,              # replays params, calibration, frames,
        "slo_ms": slo_ms,          # schedules and every fault program
        "miss_target": miss_target,
        "max_factor": max_factor,
        "refine_iters": refine_iters,
        "load_factor": load_factor,
        "scenarios": list(scenarios),
        "faults": list(faults),
        "admission_control": admission_control,
        "flush_guard_ms": flush_guard_ms,
        "device_count": jax.device_count(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_version": jax.__version__,
        "backend": jax.devices()[0].platform,
        "host": platform.machine(),
        "models": {},
    }
    knee_common = dict(frames=knee_frames, batch=batch, stages=stages,
                       seed=seed, miss_target=miss_target,
                       refine_iters=refine_iters, max_factor=max_factor,
                       flush_guard_ms=flush_guard_ms,
                       admission_control=admission_control, verbose=True)
    for model in models:
        prog = compile_for_serving(model, bits=8, seed=seed)
        # Uniform baseline knee first: it resolves the SLO every other
        # row pins (re-deriving per scenario would measure a different
        # contract per row and make the knee ratios meaningless).
        base = serve_knee(model, slo_ms=slo_ms, scenario=None,
                          program=prog, **knee_common)
        pinned_slo = base["slo_ms"]
        srows = {"uniform": base}
        for s in scenarios:
            srows[s] = serve_knee(model, slo_ms=pinned_slo, scenario=s,
                                  program=prog, **knee_common)
        emit(f"serve_chaos/{model}/scenario_knees", 0.0,
             "|".join(f"{s}={r['knee_qps']}qps"
                      + (f"(x{r['knee_of_steady']})"
                         if r["knee_of_steady"] is not None else "")
                      for s, r in srows.items()))
        frows = {}
        for fault in faults:
            frows[fault] = bench_fault(
                model, prog, fault, batch=batch, stages=stages,
                frames=chaos_frames, seed=seed, slo_ms=pinned_slo,
                miss_target=miss_target, load_factor=load_factor,
                flush_guard_ms=flush_guard_ms,
                admission_control=admission_control)
            r = frows[fault]
            emit(f"serve_chaos/{model}/{fault}", 0.0,
                 f"hung={r['hung']}|resolved={r['resolved']}"
                 f"/{r['submitted']}|failed={r['failed']}|"
                 f"recovered_s={r['recovery']['recovered_s']}")
        data["models"][model] = {
            "slo_ms": pinned_slo,
            "uniform_knee_qps": base["knee_qps"],
            "scenarios": srows,
            "faults": frows,
        }
    with open(out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"\n[serve_chaos_bench] wrote {out} ({len(data['models'])} "
          f"model(s), {1 + len(scenarios)} arrival scenario(s), "
          f"{len(faults)} fault(s), batch {batch})")
    return data


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="AlexNet only, small batch, fewer scenarios "
                         "(CI bench-smoke)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--frames", type=int, default=None,
                    help="stream length for both parts (default: "
                         "per-part multiple of batch)")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0,
                    help="params/calibration/stream/schedule/fault seed")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="interactive-class deadline (default: derived "
                         "by the uniform baseline knee)")
    ap.add_argument("--miss-target", type=float,
                    default=DEFAULT_MISS_TARGET,
                    help="armed-class miss rate defining 'sustained' "
                         "and 'recovered' (default 0.05)")
    ap.add_argument("--max-factor", type=float, default=8.0,
                    help="knee sweep cap as a multiple of steady fps")
    ap.add_argument("--refine-iters", type=int, default=None,
                    help="knee bisection refinements (default 3, "
                         "1 with --quick)")
    ap.add_argument("--load-factor", type=float,
                    default=DEFAULT_LOAD_FACTOR,
                    help="fault-replay arrival rate as a fraction of "
                         "fleet steady fps (default 0.5)")
    ap.add_argument("--flush-guard-ms", type=float, default=None,
                    help="fixed flush guard (default: adaptive)")
    ap.add_argument("--no-admission", action="store_true",
                    help="disable estimated-wait admission control")
    ap.add_argument("--scenario", action="append", default=None,
                    dest="scenarios", choices=ADVERSARIAL_SCENARIOS,
                    help="adversarial arrival scenario(s) to knee-sweep "
                         "(default: all; uniform baseline always runs)")
    ap.add_argument("--fault", action="append", default=None,
                    dest="faults", choices=FAULTS,
                    help="fault replay(s) to run (default: all)")
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
        miss_target=args.miss_target, refine_iters=args.refine_iters,
        max_factor=args.max_factor, load_factor=args.load_factor,
        flush_guard_ms=args.flush_guard_ms,
        admission_control=not args.no_admission,
        scenarios=args.scenarios, faults=args.faults)
    print_csv(csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
