"""The program on residual graphs, held to the plain reference
(``bench/reference/cnn.py``) with exact equality: its int32 output
accumulators, its calibrated exponents, its stage cuts and its stage
runners chained at every legal cut. Weights, calibration frame and
frames are seeded draws."""

import copy
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import graph, harness
from bench.reference import cnn as reference
from repro.compiler import import_source, quantize
from repro.core import workload as W
from repro.core.program import EngineStep, compile_model
from repro.serving.partition import (legal_cuts, partition_from_boundaries,
                                     partition_program)

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
CONFIGS = HERE.parent / "configs"


def _cfg(name: str) -> dict:
    path = HERE / f"{name}.json"
    return json.loads((path if path.exists()
                       else CONFIGS / f"{name}.json").read_text())


def _resnet50_by_16() -> dict:
    """ResNet-50 v1.5's whole topology (stem, max pool, 16 bottlenecks,
    4 projections, global average pool, fc) with every conv's width over
    16, at 32x32, where the global pool's window is 1x1."""
    cfg = copy.deepcopy(_cfg("resnet50"))
    cfg["graph"]["input"]["hw"] = 32
    for n in cfg["graph"]["nodes"]:
        if n["op"] == "conv":
            n["out_channels"] //= 16
        if n["op"] == "avgpool":
            n["kernel"] = 1
    return cfg


def _draws(cfg: dict, seed: int, frames: int):
    """Seeded float weights (N(0,1)/sqrt(fan_in), biases N(0, 0.05)),
    one calibration frame and ``frames`` frames, drawn on the host."""
    net = graph.parse(cfg)
    rng = np.random.default_rng(seed)
    params = {}
    for n in net.compute():
        w = rng.standard_normal(n.weight_shape, dtype=np.float32)
        params[n.name] = {
            "w": w / np.float32(np.sqrt(np.prod(n.weight_shape[:-1]))),
            "b": np.float32(0.05) * rng.standard_normal(
                n.weight_shape[-1], dtype=np.float32)}
    calib = rng.standard_normal((1,) + net.frame, dtype=np.float32)
    pool = rng.standard_normal((frames,) + net.frame, dtype=np.float32)
    return params, calib, pool


def _compiled(cfg: dict, seed: int, frames: int = 4):
    params, calib, pool = _draws(cfg, seed, frames)
    model, _ = import_source(cfg["graph"])
    prog = quantize(model, params, bits=8, calib=calib)
    return prog, reference.build(cfg, params, calib), params, calib, pool


@pytest.fixture(scope="module")
def tiny():
    return _compiled(_cfg("tiny_residual"), seed=2 ** 31 + 11)


@pytest.mark.parametrize("route", ["f32", "oracle", "kernel"])
def test_tiny_residual_accumulators_equal_the_reference(tiny, route):
    prog, net, _, _, pool = tiny
    runner = prog.compile_runner(route=route)
    got = np.asarray(runner(runner.quantize(pool)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, reference.accumulators(net, pool))
    np.testing.assert_array_equal(prog.out_scale(), net.out_scale)


def test_eager_run_equals_the_reference(tiny):
    prog, net, _, _, pool = tiny
    np.testing.assert_array_equal(prog.run(pool),
                                  reference.logits(net, pool))


def test_resnet50_by_16_accumulators_equal_the_reference():
    """The whole ResNet-50 v1.5 topology, narrowed: 53 convs, 16 adds,
    and legal stage cuts only in the stem, at the 16 block outputs and
    in the head."""
    cfg = _resnet50_by_16()
    prog, net, _, _, pool = _compiled(cfg, seed=2 ** 31 + 5)
    kinds = [s.kind for s in prog.steps]
    assert (kinds.count("conv"), kinds.count("add")) == (53, 16)
    runner = prog.compile_runner(route="f32")
    np.testing.assert_array_equal(np.asarray(runner(runner.quantize(pool))),
                                  reference.accumulators(net, pool))
    cut_after = [prog.steps[i - 1].name for i in legal_cuts(prog)]
    blocks = [n["name"] for n in cfg["graph"]["nodes"] if n["op"] == "add"]
    assert cut_after == ["conv1", "pool1"] + blocks + ["pool"]


def test_calibrated_exponents_equal_the_reference(tiny):
    """Every tensor's exponent, the weights' formats, biases and shifts
    as the reference freezes them from the same float forward."""
    prog, net, params, calib, _ = tiny
    amax = reference.calibrate(graph.parse(_cfg("tiny_residual")), params,
                               calib)
    assert prog.e_input == net.e_input
    by_name = {lyr.node.name: lyr for lyr in net.layers}
    for step in prog.steps:
        lyr = by_name[step.name]
        if step.kind in ("conv", "fc", "add"):
            assert step.e_out == reference._exponent(amax[step.name], 8)
        if step.kind == "add":
            assert step.align == lyr.align and step.shift == lyr.shift
        elif step.wq is not None:
            np.testing.assert_array_equal(step.e_in + step.e_w, lyr.acc_e)
            np.testing.assert_array_equal(step.wq, lyr.wq)
            np.testing.assert_array_equal(step.bias_q, lyr.bias)
            np.testing.assert_array_equal(step.shift, lyr.shift)


def test_stage_runners_chained_at_every_legal_cut_equal_the_whole(tiny):
    prog, _, _, _, pool = tiny
    whole = prog.compile_runner(route="f32")
    want = np.asarray(whole(whole.quantize(pool)))
    n = len(prog.steps)
    cuts = legal_cuts(prog)
    assert cuts
    for c in cuts:
        first = prog.compile_stage_runner(0, c, route="f32")
        second = prog.compile_stage_runner(c, n, route="f32")
        np.testing.assert_array_equal(
            np.asarray(second(first(first.quantize(pool)))), want)
    part = partition_program(prog, 2)
    assert set(part.boundaries[1:-1]) <= set(cuts)


def test_a_cut_across_a_skip_tensor_is_refused_naming_it(tiny):
    prog, _, _, _, _ = tiny
    names = [s.name for s in prog.steps]
    n = len(names)
    cut = names.index("b1_c2")      # pool1 is live until b1_proj reads it
    assert cut not in legal_cuts(prog)
    with pytest.raises(ValueError, match="'pool1'"):
        partition_from_boundaries(prog, (0, cut, n))
    with pytest.raises(ValueError, match="'pool1'"):
        prog.compile_stage_runner(cut, n)
    cut = names.index("b2_c3")      # b1_add is live until b2_add
    with pytest.raises(ValueError, match="'b1_add'"):
        partition_from_boundaries(prog, (0, cut, n))


# The partitions of the paper's chain models before residual graphs came
# into the program: every cut of a chain is legal, so they stay.
CHAIN_BOUNDARIES = {
    "vgg16": {1: (0, 21), 2: (0, 11, 21), 3: (0, 7, 14, 21),
              4: (0, 6, 11, 16, 21)},
    "alexnet": {1: (0, 11), 2: (0, 6, 11), 3: (0, 4, 8, 11),
                4: (0, 4, 6, 9, 11)},
    "zf": {1: (0, 11), 2: (0, 6, 11), 3: (0, 5, 9, 11),
           4: (0, 4, 6, 9, 11)},
    "yolo": {1: (0, 30), 2: (0, 16, 30), 3: (0, 12, 21, 30),
             4: (0, 10, 16, 23, 30)},
}


@pytest.mark.parametrize("name", sorted(CHAIN_BOUNDARIES))
def test_chain_partitions_are_unchanged(name):
    """At the serving budget (``compiler.quantize``'s theta), from the
    plan's cycles: steps carry only names, kinds and sources here."""
    model = W.CNN_MODELS[name]()
    prog = compile_model(model, theta=2 * 900 - len(model.layers),
                         bram_total=None)
    prog.steps = [EngineStep(name=lyr.name, kind=lyr.kind, layer=lyr,
                             pad=(0, 0), inputs=src)
                  for lyr, src in zip(model.layers, model.sources())]
    assert legal_cuts(prog) == list(range(1, len(prog.steps)))
    got = {k: partition_program(prog, k).boundaries
           for k in CHAIN_BOUNDARIES[name]}
    assert got == CHAIN_BOUNDARIES[name]


def test_add_and_avgpool_carry_named_scopes_in_the_lowered_hlo(tiny):
    prog, _, _, _, pool = tiny
    runner = prog.compile_runner(route="f32")
    text = runner.fn.lower(runner.quantize(pool), runner.weights).as_text(
        debug_info=True)
    for name in ("b1_add", "b2_add", "pool"):
        assert f"serve_tiny_residual_0_{len(prog.steps)})/{name}/" in text


def test_a_residual_graph_runs_correct_through_the_harness():
    """tiny_residual.json (every ResNet-50 v1.5 node kind: fan-out, a
    projection and an identity shortcut, adds, declared pads, a global
    average pool) goes through the compiler's front door and is served
    through ``Server``, every answer equal to the reference's."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    r = harness.run_cell(bench, {"name": "any"}, _cfg("tiny_residual"),
                         {"loop": "closed", "clients": 4}, seed=2 ** 31 + 3,
                         seconds=0.5, trace=False,
                         devices=jax.devices()[:1], t_start=0.0, peaks=None)
    assert r["correct"] is True
    assert r["checks"]["max_gap_lsb"]["value"] == 0
    assert r["attempted"] > 0 and r["failed"] == 0
