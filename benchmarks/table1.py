"""Table I reproduction: utilization & performance for VGG16 / AlexNet /
ZF / YOLO on a ZC706-class budget (900 DSPs @ 200 MHz), vs the paper's
reported numbers and our models of baselines [1] and [3].

Every row is derived from a compiled :class:`EngineProgram` — the same
object the executor runs — so the reported cycles and the executed
arithmetic come from one plan."""

from __future__ import annotations

import functools
import time

from benchmarks.baselines import (dnnbuilder_allocate, recurrent_efficiency,
                                  winograd_fused_model)
from repro.core import throughput as T
from repro.core import workload as W
from repro.core.program import compile_model
from repro.core.simulator import simulate
from repro.launch.compile_cache import enable_compile_cache

PAPER = {  # model: (DSP, eff, fps16, gops16, fps8, gops8)
    "vgg16": (900, 0.980, 11.3, 353, 22.6, 706),
    "alexnet": (864, 0.904, 230, 312, 459, 624),
    "zf": (892, 0.908, 138.4, 324, 276.8, 648),
    "yolo": (892, 0.984, 8.8, 351, 17.5, 702),
}
PAPER_GOP = {  # model complexity the paper quotes (GOP, 2 ops/MAC)
    "vgg16": 30.94, "alexnet": 1.45, "zf": 2.34, "yolo": 40.14,
}
PAPER_BASELINES_VGG = {  # reference: (DSP, eff, gops16)
    "[1] recurrent": (780, 0.585, 137),
    "[2] fused": (824, 0.696, 230),
    "[3] DNNBuilder": (680, 0.962, 262),
}

FREQ = 200e6
THETA = 900


@functools.lru_cache(maxsize=None)
def modeled_row(model: str) -> dict:
    """The analytic Table-I columns for one model, from plan-only compiles
    of the same :class:`EngineProgram` the executor runs — the "modeled"
    side that ``benchmarks/serve_bench.py`` records next to measured FPS.
    Cached: ``run.py all`` consumes it from both table1 and serve_bench."""
    m = W.CNN_MODELS[model]()
    # ---- 16-bit: 1 multiplier per DSP (plan-only compile: Alg. 1 + 2)
    t0 = time.time()
    p16 = compile_model(m, theta=THETA, bits=16, bram_total=545,
                        bandwidth_bytes=4.2e9, freq_hz=FREQ)
    alloc_us = (time.time() - t0) * 1e6
    a16 = p16.allocs
    # ---- 8-bit: 2 multipliers per DSP (paper's efficiency regime);
    # compute allocation only, as in Table I's efficiency columns.
    p8 = compile_model(m, theta=2 * THETA - len(m.layers), bits=8,
                       bram_total=None, freq_hz=FREQ)
    a8 = p8.allocs
    # ---- simulator cross-check on the same program object
    sim = simulate(p16, n_frames=3)
    return {
        "gop": m.gop,
        "alloc_us": alloc_us,
        "dsp16": T.dsps_used(a16),
        "eff16": T.dsp_efficiency(a16),
        "fps16": p16.fps(),
        "gops16": T.gops(a16, freq_hz=FREQ),
        "dsp8": T.dsps_used(a8, macs_per_dsp=2),
        "eff8": T.dsp_efficiency(a8, macs_per_dsp=2),
        "fps8": p8.fps(),
        "gops8": T.gops(a8, freq_hz=FREQ),
        "sim_eff": sim.dsp_efficiency,
    }


def run(emit, models: list[str] | None = None, quick: bool = False):
    """Print the Table-I reproduction. ``quick`` restricts to AlexNet and
    skips the VGG16 baseline / BRAM sections (the CI smoke setting)."""
    if models is None:
        models = ["alexnet"] if quick else list(W.CNN_MODELS)
    rows = []
    for model in models:
        r = modeled_row(model)
        p = PAPER[model]
        gop_ok = abs(r["gop"] - PAPER_GOP[model]) / PAPER_GOP[model] < 0.02
        emit(f"table1/{model}/alloc", r["alloc_us"],
             f"gop={r['gop']:.2f}|paper_gop_ok={gop_ok}")
        rows.append((model, r["dsp16"], r["eff16"], r["fps16"], r["gops16"],
                     r["dsp8"], r["eff8"], r["fps8"], r["gops8"],
                     r["sim_eff"], p))
    print("\n== Table I reproduction (This Work columns) ==")
    print(f"{'model':9s} {'DSP':>4s} {'eff16':>6s} {'fps16':>7s} "
          f"{'gops16':>7s} {'eff8':>6s} {'fps8':>7s} {'gops8':>7s} "
          f"{'sim_eff':>7s} | paper: DSP eff fps16 gops16 fps8 gops8")
    for (model, dsp16, eff16, fps16, gops16, dsp8, eff8, fps8, gops8,
         sim_eff, p) in rows:
        print(f"{model:9s} {dsp16:4d} {eff16:6.3f} {fps16:7.1f} "
              f"{gops16:7.0f} {eff8:6.3f} {fps8:7.1f} {gops8:7.0f} "
              f"{sim_eff:7.3f} | {p[0]:4d} {p[1]:.3f} {p[2]:6.1f} "
              f"{p[3]:4d} {p[4]:6.1f} {p[5]:4d}")
    if quick:
        return rows

    # ---- baselines on VGG16 (the paper's headline comparison)
    l16 = W.vgg16().layer_workloads(weight_bits=16)
    eff_r, cyc_r = recurrent_efficiency(l16)
    gops_r = 2 * sum(l.macs for l in l16) * (150e6 / cyc_r) / 1e9
    th_d, bound_d = dnnbuilder_allocate(l16, THETA)
    frame_d = max(bound_d, 0.0)
    gops_d = 2 * sum(l.macs for l in l16) * (FREQ / frame_d) / 1e9
    eff_d = 2 * sum(l.macs for l in l16) / (2 * th_d * frame_d)
    ours = T.gops(compile_model(W.vgg16(), theta=THETA, bits=16).allocs,
                  freq_hz=FREQ)
    print("\n== VGG16 vs baselines (modeled / paper-reported) ==")
    print(f"[1] recurrent  : eff={eff_r:.3f} gops16={gops_r:5.0f}"
          f"  (paper-reported: eff=0.585 gops=137 @150MHz)")
    print(f"[3] DNNBuilder : theta={th_d} eff={eff_d:.3f} "
          f"gops16={gops_d:5.0f}  (paper-reported: 680 DSP, eff=0.962, "
          f"gops=262)")
    gops_w, _ = winograd_fused_model(l16)
    print(f"[2] Winograd   : gops16(eff)={gops_w:5.0f}  (paper-reported: "
          f"230 @100MHz, 824 DSP, eff=0.696)")
    print(f"This work      : gops16={ours:5.0f}  -> speedup vs [1] "
          f"{ours/gops_r:.2f}x (paper claims 2.58x), vs [2] "
          f"{ours/gops_w:.2f}x (paper claims 1.53x), vs [3] "
          f"{ours/gops_d:.2f}x (paper claims 1.35x)")
    emit("table1/vgg16/speedup_vs_recurrent", 0.0,
         f"{ours/gops_r:.2f}x_vs_paper_2.58x")
    emit("table1/vgg16/speedup_vs_dnnbuilder", 0.0,
         f"{ours/gops_d:.2f}x_vs_paper_1.35x")
    emit("table1/vgg16/speedup_vs_winograd", 0.0,
         f"{ours/gops_w:.2f}x_vs_paper_1.53x")

    # ---- Algorithm 2: BRAM / bandwidth row (Table I "BRAM")
    from repro.core.allocator import total_bram, weight_traffic_per_frame
    paper_bram = {"vgg16": 0.74, "alexnet": 0.84, "zf": 0.58, "yolo": 0.76}
    print("\n== Algorithm 2: BRAM/bandwidth (1090 BRAM18, 4.2 GB/s DDR) ==")
    for model, fn in W.CNN_MODELS.items():
        allocs = compile_model(fn(), theta=THETA, bits=16, bram_total=1090,
                               bandwidth_bytes=4.2e9, freq_hz=FREQ,
                               bram_weights=True).allocs
        act18 = total_bram(allocs, act_bytes=2)
        bram18 = total_bram(allocs, act_bytes=2, weights=True)
        n_res = sum(a.weights_resident for a in allocs)
        traffic = sum(weight_traffic_per_frame(a) for a in allocs
                      if a.layer.kind == "conv")
        bw = T.pipeline_fps(allocs, freq_hz=FREQ) * traffic / 1e9
        print(f"  {model:8s} BRAM {bram18/1090:4.0%} (act {act18}, weight "
              f"{bram18 - act18}, {n_res} resident weight set(s); paper "
              f"total {paper_bram[model]:.0%}), DDR {bw:.1f} GB/s")
        emit(f"table1/{model}/bram", 0.0,
             f"{bram18}of1090|paper={paper_bram[model]}")
    return rows


def main(argv=None) -> int:
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description="Table I reproduction")
    ap.add_argument("--quick", action="store_true",
                    help="AlexNet only, no baseline/BRAM sections (CI)")
    ap.add_argument("--model", action="append", default=None,
                    choices=sorted(W.CNN_MODELS), dest="models")
    args = ap.parse_args(argv)
    from benchmarks.run import print_csv
    csv: list[str] = []

    def emit(name, us, derived=""):
        csv.append(f"{name},{us:.1f},{derived}")

    run(emit, models=args.models, quick=args.quick)
    print_csv(csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
