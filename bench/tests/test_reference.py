"""The plain reference computes what the program computes: the program's
int32 oracle route on the goldens' frames, and on the benchmark's own
draws (with biases) for a small model; its int4 control does not. A
chain written as a graph is the same network; on a residual graph the
add and the average pool are exact, follow the float graph, and see a
dropped or misaligned shortcut."""

import json
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, graph, inputs, workcount
from bench.reference import cnn as reference
from repro.compiler import golden_frames, load_golden, quantize
from repro.compiler.calibrate import calib_batch
from repro.core import workload as W
from repro.models import cnn

HERE = Path(__file__).resolve().parent
GOLDEN = HERE.parents[1] / "tests" / "golden"
TINY = json.loads((HERE / "tiny.json").read_text())
TINY_GRAPH = json.loads((HERE / "tiny_graph.json").read_text())
RESIDUAL = json.loads((HERE / "tiny_residual.json").read_text())


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


@pytest.mark.parametrize("cfg", [TINY, RESIDUAL], ids=["chain", "residual"])
def test_int4_control_fails_the_check(cfg):
    """The control, the reference with int4 weights put in the program's
    place, reads far above the limit on every seed."""
    for seed in (1, 2, 3):
        params, calib, pool = inputs.make_inputs(cfg, seed)
        net = reference.build(cfg, params, calib)
        low = reference.build(cfg, params, calib, weight_bits=4)
        gap = check.gap_lsb(reference.logits(low, pool),
                            reference.logits(net, pool), net.out_scale)
        assert gap > 3 * max(1, check.LIMITS["max_gap_lsb"])


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_chain_written_as_a_graph_is_the_chain(seed):
    """tiny.json and tiny_graph.json draw the same values, count the same
    work and give the same accumulators."""
    chain, as_graph = inputs.make_inputs(TINY, seed), inputs.make_inputs(
        TINY_GRAPH, seed)
    assert list(chain[0]) == list(as_graph[0])
    for name in chain[0]:
        for k in ("w", "b"):
            assert np.array_equal(chain[0][name][k], as_graph[0][name][k])
    assert np.array_equal(chain[1], as_graph[1])
    assert np.array_equal(chain[2], as_graph[2])
    assert workcount.layer_work(TINY) == workcount.layer_work(TINY_GRAPH)
    a = reference.accumulators(reference.build(TINY, *chain[:2]), chain[2])
    b = reference.accumulators(reference.build(TINY_GRAPH, *as_graph[:2]),
                               as_graph[2])
    assert np.array_equal(a, b)


# (a, e_a, b, e_b, e_out, relu) -> the int8 sum, worked out by hand.
ADD_CASES = [
    # 3 * 2^-4 + 5 * 2^-2 = 23 * 2^-4; to 2^-3 by a floor shift: 11.
    (3, -4, 5, -2, -3, False, 11),
    # the same, negative: -23 * 2^-4 -> floor(-11.5) = -12.
    (-3, -4, -5, -2, -3, False, -12),
    # operands of mixed sign: -7 * 2^-1 + 9 * 2^-3 = -19 * 2^-3 -> -10.
    (-7, -1, 9, -3, -2, False, -10),
    # ReLU before the shift: -19 * 2^-3 -> 0.
    (-7, -1, 9, -3, -2, True, 0),
    # saturation: 127 + 127 at one exponent stays 254 in int32 -> 127.
    (127, -2, 127, -2, -2, False, 127),
    (-128, -2, -128, -2, -2, False, -128),
    # a finer output exponent: a saturating left shift, 4 * 2^1 = 8.
    (3, -2, 1, -2, -3, False, 8),
    (100, -2, 100, -2, -3, False, 127),
]


@pytest.mark.parametrize("a,e_a,b,e_b,e_out,relu,want", ADD_CASES)
def test_add_is_exact_on_hand_cases(a, e_a, b, e_b, e_out, relu, want):
    align, shift = reference.add_format(e_a, e_b, e_out)
    got = reference.add(jnp.int8(a), jnp.int8(b), align, shift, relu)
    assert got.dtype == jnp.int8 and int(got) == want


def test_add_refuses_operands_too_far_apart():
    assert reference.add_format(0, -23, 0) == ((23, 0), 23)
    with pytest.raises(ValueError, match="24|23"):
        reference.add_format(0, -24, 0)


# (input rows, kernel, stride, pad) -> the int8 average, by hand.
AVG_CASES = [
    ([[1, 0], [0, 1]], 2, 2, 0, 1),             # 2/4 = 0.5 rounds up
    ([[-1, 0], [0, -1]], 2, 2, 0, 0),           # -0.5 rounds up, to 0
    ([[2, 1], [1, 2]], 2, 2, 0, 2),             # 1.5 -> 2
    ([[-2, -1], [-1, -2]], 2, 2, 0, -1),        # -1.5 -> -1
    ([[1, 1], [1, 0]], 2, 2, 0, 1),             # 0.75 -> 1
    ([[-1, -1], [-1, 0]], 2, 2, 0, -1),         # -0.75 -> -1
    ([[1, 0], [0, 0]], 2, 2, 0, 0),             # 0.25 -> 0
    ([[127, 127], [127, 127]], 2, 2, 0, 127),
    ([[-128, -128], [-128, -128]], 2, 2, 0, -128),
    # a 3x3 window at pad 1 holds the four values and five zeros:
    ([[2, 1], [1, 1]], 3, 2, 1, 1),             # 5/9 = 0.56 -> 1
    ([[1, 1], [1, 1]], 3, 2, 1, 0),             # 4/9 = 0.44 -> 0
    ([[-2, -1], [-1, -1]], 3, 2, 1, -1),        # -5/9 -> -1
    ([[-1, -1], [-1, -1]], 3, 2, 1, 0),         # -4/9 -> 0
]


@pytest.mark.parametrize("rows,k,stride,pad,want", AVG_CASES)
def test_avgpool_rounds_half_up_on_hand_cases(rows, k, stride, pad, want):
    node = graph.Node("avgpool", "p", ("input",), (2, 2, 1), (1, 1, 1),
                      kernel=k, stride=stride, pad=(pad, pad))
    x = jnp.asarray(np.asarray(rows, np.int8).reshape(1, 2, 2, 1))
    got = reference.avgpool(x, node)
    assert got.dtype == jnp.int8 and got.shape == (1, 1, 1, 1)
    assert int(got.reshape(())) == want


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 7])
def test_residual_logits_follow_the_float_graph(seed):
    """The integer logits times their scale lie within a fifth of the
    largest float logit of the same graph on the same pool. Each of the
    ten int8 tensors on the longest path (the input, five convs and the
    two adds' operands and sums) errs by under one step, at most 2/127
    (1.6%) of its calibrated range, and the draws' fan-in scaling keeps a
    layer's gain near one: ten steps make 16%, and pool frames beyond the
    calibration frame saturate a little more. Measured: 6-12% over seeds
    1-12; a chain of three layers reads 4-8%."""
    params, calib, pool = inputs.make_inputs(RESIDUAL, seed)
    got = reference.logits(reference.build(RESIDUAL, params, calib), pool)
    want = reference.float_forward(graph.parse(RESIDUAL), params, pool)
    err = np.abs(got.reshape(want.shape) - want).max()
    assert err <= 0.2 * np.abs(want).max()


ADD = reference.add


def _drop_shortcut(a, b, align, shift, relu):
    return ADD(a, jnp.zeros_like(b), align, shift, relu)


def _misalign(a, b, align, shift, relu):
    return ADD(a, b, (align[0], align[1] + 1), shift, relu)


@pytest.mark.parametrize("fault", [_drop_shortcut, _misalign],
                         ids=["dropped_shortcut", "misaligned_by_one_bit"])
def test_residual_faults_change_the_integers(fault, monkeypatch):
    """A shortcut left out of both adds, or its operand shifted one bit
    too far, changes the accumulators on every seed tried."""
    for seed in (1, 2, 3, 4):
        params, calib, pool = inputs.make_inputs(RESIDUAL, seed)
        net = reference.build(RESIDUAL, params, calib)
        sound = reference.accumulators(net, pool)
        with monkeypatch.context() as m:
            m.setattr(reference, "add", fault)
            broken = reference.accumulators(net, pool)
        assert not np.array_equal(sound, broken), seed
