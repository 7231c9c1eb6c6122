"""Find a cell's knee once, on the chip: not part of a benchmark run.

    python3 bench/sweep.py --config alexnet --seed 5 --seconds 20 \\
        --clients 16 32 64 128

One process starts the configuration's server as a run does, then
drives it segment by segment: an open Poisson loop at each rate in
``--rates`` and a closed loop at each count in ``--clients``. Each
segment prints one JSON line: frames/s answered, latency from the due
time, how many requests were still outstanding when the segment's
schedule ended, and the latency limit the program's own knee search
uses, (K x R + 3) batch windows at the calibrated steady rate (copied
from ``_derived_slo_ms`` in ``repro.serving.server``). A rate is
sustained when its p95 stays under that limit and the backlog at the end
is no more than that many batch windows' worth of frames.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def slo_ms(stages: int, replicas: int, batch: int, steady: float) -> float:
    return (stages * replicas + 3) * 1e3 * batch / max(steady, 1e-9)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--clients", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    import numpy as np

    from bench import drive, generator, harness

    cfg = harness.load_json(ROOT / "bench" / "configs" / f"{args.config}.json")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cfg["chips"]:
        print("the sweep needs the configuration's chips", file=sys.stderr)
        return 1
    st = harness.start(cfg, args.seed, devices[0])
    srv = cfg["server"]
    steady = st.server.runtime(st.name).steady_fps
    limit = slo_ms(srv["stages"], srv["replicas"], st.batch, steady)
    harness.log(f"setup {st.split}; calibrated steady {steady:.1f} frames/s; "
                f"latency limit {limit:.1f} ms")
    try:
        segments = ([{"loop": "open", "scenario": "poisson", "rate_fps": r}
                     for r in args.rates]
                    + [{"loop": "closed", "clients": c} for c in args.clients])
        for i, traffic in enumerate(segments):
            plan = generator.make_plan(traffic, seed=args.seed + i,
                                       seconds=args.seconds,
                                       pool=len(st.pool))
            harness.warm_up(st, plan)
            t0 = time.perf_counter()
            if plan.loop == "open":
                sent = drive.open_loop(st.server, st.name, st.pool, plan, t0)
                time.sleep(max(0.0, t0 + args.seconds - time.perf_counter()))
            else:
                sent = drive.closed_loop(st.server, st.name, st.pool, plan,
                                         t_end=t0 + args.seconds)
            t1 = time.perf_counter()
            backlog = sum(1 for s in sent if not s.done())
            drive.wait_all(sent, t1 + 120)
            done = [s for s in sent if s.answered]
            lat = np.asarray([s.t_done - s.due for s in done]) * 1e3
            in_window = sum(1 for s in done if s.t_done <= t1)
            row = dict(traffic, frames_per_s=in_window / (t1 - t0),
                       sent=len(sent), answered=len(done),
                       backlog_at_end=backlog,
                       p50_ms=float(np.percentile(lat, 50)),
                       p95_ms=float(np.percentile(lat, 95)),
                       limit_ms=limit, steady_fps=steady)
            row["sustained"] = bool(
                row["p95_ms"] <= limit and len(done) == len(sent)
                and backlog <= limit / 1e3 * steady)
            print(json.dumps(row), flush=True)
    finally:
        st.server.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
