"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload alexnet.closed --seed 7 --seconds 10 --trace 0

The cell is looked up by name in ``BENCHMARK.json``. The run draws its
weights, calibration frame and frame pool from ``--seed``, compiles the
configuration through the program's compiler, starts the server with
``build_server``, warms it up, then sends the cell's traffic through
``Server.submit`` for ``--seconds``. Afterwards it checks every answer
against the plain reference. The last line of standard output is the
result as one JSON object: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics from a profiler trace of the window
with ``--trace 1``.

It exits non-zero, printing no result, where JAX finds no TPU, fewer
chips than the cell asks for, or a device kind ``bench/peaks.json`` does
not list. JAX's persistent compile cache lives in ``.jax_cache/`` of the
checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "bench-tpu-logs"))
    from bench import harness

    bench, cell, cfg, traffic = harness.load_cell(args.workload, ROOT)
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    peaks = harness.load_json(ROOT / "bench" / "peaks.json").get(
        devices[0].device_kind)
    if peaks is None:
        print(f"bench/peaks.json has no row for {devices[0].device_kind!r}",
              file=sys.stderr)
        return 1
    result = harness.run_cell(
        bench, cell, cfg, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices[:cell["chips"]],
        t_start=T_START, peaks=peaks)
    print(json.dumps(result), flush=True)
    harness.log_checks(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
