"""Benchmark harness — one module per paper table/figure.

  table1    : paper Table I (4 CNNs on ZC706-class budget) + baselines
  serve     : measured-vs-modeled serving FPS (jitted batched executor
              vs eager loop vs Algorithm 1) -> BENCH_serve.json
  serve-async : single-jit vs K-stage pipelined serving (throughput +
              request latency percentiles) -> BENCH_serve_async.json
  serve-qos : mixed traffic classes at two arrival rates (per-class
              queueing/assembly/compute split, SLO miss + drop rates)
              -> BENCH_serve_qos.json
  serve-knee : bracketing absolute-QPS sweep; the knee (max sustained
              rate with interactive SLO miss < 1%) is the headline
              capacity number -> BENCH_serve_knee.json
  serve-multi : multi-tenant model zoo behind one frontend (aggregate
              mixed-traffic knee + tenant-isolation flood)
              -> BENCH_serve_multi.json
  serve-chaos : fault injection + adversarial traffic (replica kill /
              straggler / bus-drop replays gated on liveness, plus
              knee sweeps under hostile arrival processes)
              -> BENCH_serve_chaos.json
  import-smoke : compiler front door on examples/lenet.json (import ->
              cross-route golden check -> serve smoke); not part of
              ``all`` — it is a gate, not a measurement
  ablation  : allocator objectives (paper greedy / exact / waterfill)
              + pipeline stage balance on the TPU mesh
  roofline  : three-term roofline per (arch x shape x mesh) cell
  kernels   : Pallas kernel microbenches (interpret-mode correctness +
              wall time of the jnp oracle path on CPU)

Usage: ``python benchmarks/run.py [which] [--quick]`` where ``which`` is
one of the names above or ``all``. ``--quick`` runs the reduced CI
setting (AlexNet-only table1/serve). Prints ``name,us_per_call,derived``
CSV lines (one per measurement) plus human-readable tables.
"""

from __future__ import annotations

import argparse

from repro.launch.compile_cache import enable_compile_cache

_CSV: list[str] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    line = f"{name},{us_per_call:.1f},{derived}"
    _CSV.append(line)


def print_csv(lines: list[str]) -> None:
    """The shared trailing CSV block every benchmark entry point prints
    (one format, one place — table1.main and serve_bench.main reuse it)."""
    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for line in lines:
        print(line)


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("which", nargs="?", default="all",
                    choices=("all", "table1", "serve", "serve-async",
                             "serve-qos", "serve-knee", "serve-multi",
                             "serve-chaos", "import-smoke", "ablation",
                             "roofline", "kernels"))
    ap.add_argument("--quick", action="store_true",
                    help="reduced CI setting (AlexNet-only, small batch)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="[serve-knee] pipeline replicas behind the "
                         "least-wait router")
    ap.add_argument("--replicas-sweep", default=None,
                    dest="replicas_sweep",
                    help="[serve-knee] comma list (e.g. 1,2,4): "
                         "knee-vs-R scaling sweep")
    ap.add_argument("--arrival", default="uniform",
                    choices=("uniform", "poisson"),
                    help="[serve-knee] 'poisson' adds a bursty "
                         "<model>:poisson row beside the uniform knee")
    args = ap.parse_args(argv)
    only = args.which

    if only in ("all", "table1"):
        from benchmarks import table1
        table1.run(emit, quick=args.quick)
    if only in ("all", "serve"):
        from benchmarks import serve_bench
        serve_bench.run(emit, quick=args.quick)
    if only in ("all", "serve-async"):
        from benchmarks import serve_async_bench
        serve_async_bench.run(emit, quick=args.quick)
    if only in ("all", "serve-qos"):
        from benchmarks import serve_qos_bench
        serve_qos_bench.run(emit, quick=args.quick)
    if only in ("all", "serve-knee"):
        from benchmarks import serve_knee_bench
        serve_knee_bench.run(
            emit, quick=args.quick, replicas=args.replicas,
            arrival=args.arrival,
            replicas_sweep=([int(r) for r in
                             args.replicas_sweep.split(",")]
                            if args.replicas_sweep else None))
    if only in ("all", "serve-multi"):
        from benchmarks import serve_multi_bench
        serve_multi_bench.run(emit, quick=args.quick)
    if only in ("all", "serve-chaos"):
        from benchmarks import serve_chaos_bench
        serve_chaos_bench.run(emit, quick=args.quick)
    if only == "import-smoke":
        import os
        import time

        from repro.launch.import_model import import_and_serve
        spec = os.path.join(os.path.dirname(__file__), os.pardir,
                            "examples", "lenet.json")
        t0 = time.perf_counter()
        r = import_and_serve(spec, serve_frames=6, batch=4, stages=1)
        emit("import_smoke.lenet", (time.perf_counter() - t0) * 1e6,
             f"completed={r['serve']['completed']}/6")
    if only in ("all", "ablation"):
        from benchmarks import ablation
        ablation.run_objectives(emit)
        ablation.run_stage_balance(emit)
    if only in ("all", "roofline"):
        from benchmarks import roofline
        roofline.run(emit, "pod")
        roofline.run(emit, "multipod")
    if only in ("all", "kernels"):
        from benchmarks import kernel_bench
        kernel_bench.run(emit)

    print_csv(_CSV)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
