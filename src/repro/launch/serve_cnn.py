"""CNN serving launcher: stream frames through a compiled EngineProgram.

Serves any of the four paper models (vgg16 / alexnet / zf / yolo) either
from a single jitted step chain (:class:`repro.core.executor
.EngineExecutor`) or through the stage-pipelined serving subsystem
(``--stages K``: :class:`repro.serving.PipelineExecutor` + the async
:class:`repro.serving.AsyncFrontend`), reporting measured steady-state
FPS next to the Algorithm-1 predicted FPS of the same plan (the paper's
modeled pipeline throughput on the ZC706-class budget) — plus request
latency percentiles for the async path.

With ``--qos`` (or ``--traffic-mix`` / ``--slo-ms``) the stream is a
mixed-traffic arrival process through the QoS frontend: priority lanes,
per-request deadlines with drop-on-SLO-miss, and per-class latency split
into queueing / assembly / compute — with the expedited flush and the
(default-on) estimated-wait admission control driven by an online EWMA
service-time estimate warm-started from the calibration pass.
``--knee`` instead runs the bracketing absolute-QPS sweep and reports
the capacity knee: the max sustained rate at which the interactive
class misses its SLO less than ``--miss-target`` of the time.
``--place-stages`` pins stage i to ``jax.devices()[i % n]``
(transparent on a single device). ``--replicas R`` (with
``--replica-mode pipeline|stage-shard``) serves through R routed
pipeline replicas (:class:`repro.serving.ReplicaPool`): each ready
micro-batch goes to the replica with the least estimated wait, and the
fleet's knee scales with R on a multi-device backend (force one on CPU
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

This module is the CLI only. The serving engine itself — registry,
server lifecycle, and the ``serve``/``serve_async``/``serve_qos``/
``serve_knee`` measurement paths — lives in
:mod:`repro.serving.server`; multi-model (multi-tenant) serving is
exercised by ``benchmarks/serve_multi_bench.py`` over the same engine.

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.serve_cnn --model alexnet \
      --frames 64 --batch 16
  PYTHONPATH=src python -m repro.launch.serve_cnn --model alexnet \
      --frames 64 --batch 16 --stages 2 --max-wait-ms 10
  PYTHONPATH=src python -m repro.launch.serve_cnn --model alexnet \
      --frames 64 --batch 16 --stages 2 --qos --slo-ms 200 \
      --traffic-mix "interactive:1:0.25:slo,batch:0:0.75"
"""

from __future__ import annotations

import argparse

from repro.core import workload as W
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.server import (compile_for_serving, serve, serve_async,
                                  serve_knee, serve_knee_rescale,
                                  serve_qos, synthetic_stream)

# Historical import surface: the serve paths started life in this
# module, and the benches/tests import them from here.
__all__ = ["compile_for_serving", "synthetic_stream", "serve",
           "serve_async", "serve_qos", "serve_knee", "serve_knee_rescale",
           "main"]


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="alexnet",
                    choices=sorted(W.CNN_MODELS))
    ap.add_argument("--frames", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--bits", type=int, default=8, choices=(8, 16))
    ap.add_argument("--route", default=None,
                    choices=("f32", "oracle", "kernel"),
                    help="MAC lowering (default: f32 for int8)")
    ap.add_argument("--eager-frames", type=int, default=0,
                    help="also time N frames through the eager loop")
    ap.add_argument("--output", default="top1",
                    choices=("top1", "logits"))
    ap.add_argument("--stages", type=int, default=0,
                    help="serve through the K-stage pipelined subsystem "
                         "with the async frontend (0 = single-jit path)")
    ap.add_argument("--max-wait-ms", type=float, default=None,
                    help="dynamic batcher flush timeout (async path; "
                         "default: one full-batch window at the arrival "
                         "rate)")
    ap.add_argument("--arrival-fps", type=float, default=None,
                    help="open-loop request rate (default: 70%% of the "
                         "measured pipeline throughput)")
    ap.add_argument("--place-stages", action="store_true",
                    help="pin stage i to jax.devices()[i %% n] "
                         "(transparent on a single device)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through R routed pipeline replicas "
                         "(ReplicaPool + least-estimated-wait router; "
                         "implies the pipelined subsystem)")
    ap.add_argument("--replica-mode", default="pipeline",
                    choices=("pipeline", "stage-shard"),
                    help="replica placement: whole pipeline per device, "
                         "or stages sharded across each replica's "
                         "contiguous device slice")
    ap.add_argument("--qos", action="store_true",
                    help="serve a mixed-traffic stream through the QoS "
                         "frontend (priority lanes + deadlines) and "
                         "report per-class phase-split latency")
    ap.add_argument("--knee", action="store_true",
                    help="bracketing absolute-QPS sweep: report the max "
                         "sustained rate with interactive miss rate "
                         "under --miss-target (the capacity knee)")
    ap.add_argument("--miss-target", type=float, default=0.01,
                    help="armed-class SLO miss rate defining 'sustained' "
                         "for --knee (default 0.01)")
    ap.add_argument("--no-admission", action="store_true",
                    help="disable estimated-wait admission control "
                         "(PR-4 lane-bound-only admission)")
    ap.add_argument("--flush-guard-ms", type=float, default=None,
                    help="fixed expedited-flush guard margin (default: "
                         "adaptive, 25%% of the service estimate + 2ms)")
    ap.add_argument("--traffic-mix", default=None,
                    help="QoS mix as name:priority:share[:deadline_ms] "
                         "comma-separated ('slo' = --slo-ms; default: "
                         "interactive:1:0.25:slo,batch:0:0.75)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="deadline for the default interactive class "
                         "(implies --qos)")
    ap.add_argument("--seed", type=int, default=0,
                    help="params/calibration/stream RNG seed")
    ap.add_argument("--quick", action="store_true",
                    help="small smoke setting (8 frames, batch 4)")
    args = ap.parse_args(argv)
    if args.quick:
        args.frames, args.batch = 8, 4
    qos = args.qos or args.traffic_mix is not None or args.slo_ms is not None
    if args.knee or qos:
        from repro.serving import parse_traffic_mix
        # slo_ms=None lets serve_qos derive a feasible deadline from
        # the measured service time; only an explicit --slo-ms pins it
        # (and is required when --traffic-mix uses the 'slo' token).
        mix = (parse_traffic_mix(args.traffic_mix, args.slo_ms)
               if args.traffic_mix else None)
    if args.knee:
        serve_knee(args.model, frames=args.frames, batch=args.batch,
                   stages=max(args.stages, 1), bits=args.bits,
                   route=args.route, seed=args.seed, slo_ms=args.slo_ms,
                   traffic_mix=mix, miss_target=args.miss_target,
                   max_wait_ms=args.max_wait_ms,
                   flush_guard_ms=args.flush_guard_ms,
                   admission_control=not args.no_admission,
                   place_stages=args.place_stages,
                   replicas=args.replicas,
                   replica_mode=args.replica_mode, output=args.output)
    elif qos:
        serve_qos(args.model, frames=args.frames, batch=args.batch,
                  stages=max(args.stages, 1), bits=args.bits,
                  route=args.route, seed=args.seed, slo_ms=args.slo_ms,
                  traffic_mix=mix, arrival_fps=args.arrival_fps,
                  max_wait_ms=args.max_wait_ms,
                  admission_control=not args.no_admission,
                  flush_guard_ms=args.flush_guard_ms,
                  place_stages=args.place_stages,
                  replicas=args.replicas,
                  replica_mode=args.replica_mode, output=args.output)
    elif args.stages > 0 or args.replicas > 1:
        serve_async(args.model, frames=args.frames, batch=args.batch,
                    stages=max(args.stages, 1), bits=args.bits,
                    route=args.route, max_wait_ms=args.max_wait_ms,
                    arrival_fps=args.arrival_fps, output=args.output,
                    place_stages=args.place_stages,
                    replicas=args.replicas,
                    replica_mode=args.replica_mode, seed=args.seed)
    else:
        serve(args.model, frames=args.frames, batch=args.batch,
              bits=args.bits, route=args.route, seed=args.seed,
              eager_frames=args.eager_frames, output=args.output)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
