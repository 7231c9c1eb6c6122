"""Read the control on the chip: not part of a benchmark run.

    python3 bench/control.py --config alexnet --seeds 11 12 13

For each seed it draws the run's inputs, builds the plain reference at
the configuration's int8 (a chain or a graph) and the control (the same
reference with its weights held at int4, the next precision below),
computes both over the seed's frame pool on the chip, and prints one
JSON line: the control's ``max_gap_lsb`` against the int8 reference, in
the same units the benchmark's check uses, and how long the reference
took. The control has to fail the check's limit on every seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from bench import check, harness, inputs
    from bench.reference import cnn as reference

    cfg = harness.load_json(ROOT / "bench" / "configs" / f"{args.config}.json")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("the control is read on the chip", file=sys.stderr)
        return 1
    block = cfg["server"]["batch"]
    for seed in args.seeds:
        params, calib, pool = inputs.make_inputs(cfg, seed, dev)
        t = time.perf_counter()
        net = reference.build(cfg, params, calib)
        t_build = time.perf_counter() - t
        want = reference.logits(net, pool, block=block, device=dev)
        t_ref = time.perf_counter() - t
        low = reference.build(cfg, params, calib, weight_bits=4)
        got = reference.logits(low, pool, block=block, device=dev)
        n = len(pool)
        gap = check.gap_lsb(got.reshape(n, -1), want.reshape(n, -1),
                            net.out_scale)
        print(json.dumps({"config": args.config, "seed": seed,
                          "control_max_gap_lsb": gap,
                          "limit": check.LIMITS["max_gap_lsb"],
                          "fails": gap > check.LIMITS["max_gap_lsb"],
                          "reference_build_s": t_build,
                          "reference_total_s": t_ref}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
