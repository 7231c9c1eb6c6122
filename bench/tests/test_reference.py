"""The plain reference computes what the program computes: the program's
int32 oracle route on the goldens' frames, and on the benchmark's own
draws (with biases) for a small model; its int4 control does not."""

import json
import zlib
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import check, inputs
from bench.reference import cnn as reference
from repro.compiler import golden_frames, load_golden, quantize
from repro.compiler.calibrate import calib_batch
from repro.core import workload as W
from repro.models import cnn

HERE = Path(__file__).resolve().parent
GOLDEN = HERE.parents[1] / "tests" / "golden"
TINY = json.loads((HERE / "tiny.json").read_text())


def _cfg(model) -> dict:
    return {"input_hw": model.input_hw, "input_ch": model.input_ch,
            "layers": [{"name": l.name, "kind": l.kind, "in_ch": l.in_ch,
                        "out_ch": l.out_ch, "kernel": l.kernel,
                        "stride": l.stride, "groups": l.groups,
                        "out_size": l.out_size} for l in model.layers]}


@pytest.mark.parametrize("name", ["zf", "yolo"])
def test_reference_reproduces_the_golden(name):
    """The program's seed-0 weights and calibration, fed to the
    reference, give the golden's accumulators bit for bit."""
    model = W.CNN_MODELS[name]()
    params = jax.device_get(cnn.init_params(model, jax.random.PRNGKey(0)))
    net = reference.build(_cfg(model), params,
                          np.asarray(calib_batch(model, 1, 0)))
    golden = load_golden(GOLDEN / f"{name}.npz")
    acc = reference.accumulators(net, golden_frames(model, seed=0), block=2)
    assert net.e_input == int(golden["e_input"])
    assert np.array_equal(acc[0].reshape(-1)[:len(golden["acc_sample"])],
                          golden["acc_sample"])
    assert zlib.crc32(np.ascontiguousarray(acc).tobytes()) == int(
        golden["acc_crc"])


def _tiny_model():
    return W.CNNModel("tiny", TINY["input_hw"], TINY["input_ch"],
                      tuple(W.ConvLayer(**lyr) for lyr in TINY["layers"]))


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_reference_equals_the_oracle_route_on_bench_draws(seed):
    params, calib, pool = inputs.make_inputs(TINY, seed)
    prog = quantize(_tiny_model(), params, bits=8, seed=seed, calib=calib)
    runner = prog.compile_runner(route="oracle")
    want = runner.logits(pool)
    net = reference.build(TINY, params, calib)
    assert any(np.any(s.bias_q != 0) for s in prog.steps if s.wq is not None)
    for step, lyr in zip(prog.steps, net.layers):
        if step.wq is not None:
            assert np.array_equal(step.wq, lyr.wq)
            assert np.array_equal(step.shift, lyr.shift)
            assert np.array_equal(step.bias_q, lyr.bias)
    got = reference.logits(net, pool, block=4)
    assert np.array_equal(got, want)


def test_int4_control_fails_the_check():
    """The control, the reference with int4 weights put in the program's
    place, reads far above the limit on every seed."""
    for seed in (1, 2, 3):
        params, calib, pool = inputs.make_inputs(TINY, seed)
        net = reference.build(TINY, params, calib)
        low = reference.build(TINY, params, calib, weight_bits=4)
        gap = check.gap_lsb(reference.logits(low, pool),
                            reference.logits(net, pool), net.out_scale)
        assert gap > 3 * max(1, check.LIMITS["max_gap_lsb"])
