"""Plain reference of a served int8 CNN, written from its definition.

It imports nothing of the program under test. It reads the network from
the configuration in either of its forms (``bench/graph.py``: a chain of
layers, or a graph of nodes) and, from the float weights, biases and
calibration frame that the benchmark drew from its seed, freezes the
fixed-point formats the configuration states and runs the integer
forward pass:

* activations: one power-of-two exponent per int8 tensor,
  ``e = ceil(log2(amax / qmax))`` over the calibration frame (the input,
  and the output of each conv, fc and add, after its ReLU where one
  folds into it); max and average pooling, a lone ReLU and flatten keep
  their input's exponent;
* weights: one power-of-two exponent per output channel, floored so the
  bias fits the 32-bit accumulator and the output shift stays within 31
  bits, then ``round(w / 2^e)`` clipped to int8;
* conv and fc: int8 x int8 products summed exactly in int32, plus the
  bias on the accumulator's scale, ReLU where a ``relu`` node is the
  tensor's only consumer, then an arithmetic shift by ``e_out - (e_in +
  e_w)`` (floor for a right shift, saturating for a left one) clipped to
  int8; without a ReLU the same shift applies to the signed sum. A fully
  connected node reads its input flattened in NHWC order;
* max pooling on the integers, the declared padding filled with -128;
* add: both int8 operands are aligned to the finer of their two
  exponents by left shifts in int32 (by at most 23 bits, so the sum is
  exact) and summed exactly; ReLU where a ``relu`` node is its only
  consumer; then requantized to the add's own calibrated exponent with
  the conv's shift (floor right, saturating left) and clipped to int8;
* average pooling: the window summed in int32, the declared padding
  counted as zeros, divided by the window's size ``n`` rounding half up,
  ``(s + n // 2) // n`` with floor division; the exponent is kept. A
  global pool is a window the size of its input;
* a lone ``relu`` (one that does not fold) is ``max(x, 0)`` on int8;
* the output node (a conv or fc) keeps its int32 accumulators; logits are
  those times ``2^(e_in + e_w)``, per channel, in float32.

Calibration and quantization run on the host CPU with float32 arithmetic
(the float forward of the same network op by op, so its rounding is the
host's; an average pool is the window's sum over its size). The integer
pass runs on any device: its arithmetic is exact, so every device gives
the same integers. ``weight_bits=4`` gives the control: the same network
with its weights held at 4 bits, the next precision below int8.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import graph
from bench.graph import INPUT, Net, Node

ACT_BITS = 8
MAX_ALIGN = 23      # int8 << 23, twice over, still sums inside int32


def _exponent(amax: float, bits: int) -> int:
    qmax = 2 ** (bits - 1) - 1
    return math.ceil(math.log2(max(float(amax), 1e-12) / qmax))


def _window(x, init, op, n: Node):
    k, s, (lo, hi) = n.kernel, n.stride, n.pad
    return jax.lax.reduce_window(x, init, op, (1, k, k, 1), (1, s, s, 1),
                                 ((0, 0), (lo, hi), (lo, hi), (0, 0)))


def float_forward(net: Net, params: dict, x, amax: dict | None = None):
    """The float forward of the network on the host CPU: the output
    node's values. Where ``amax`` is given, it gets the largest magnitude
    of the input and of each conv, fc and add output (after its ReLU)."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        x = jax.device_put(jnp.asarray(x, jnp.float32), cpu)
        env = {INPUT: x}
        if amax is not None:
            amax["__input__"] = float(jnp.max(jnp.abs(x)))
        for n in net.nodes:
            x = env[n.inputs[0]]
            if n.op == "maxpool":
                x = _window(x, -jnp.inf, jax.lax.max, n)
            elif n.op == "avgpool":
                x = _window(x, np.float32(0), jax.lax.add, n) / np.float32(
                    n.kernel * n.kernel)
            elif n.op == "flatten":
                x = x.reshape(x.shape[0], -1)
            elif n.op == "relu":
                x = jax.nn.relu(x)
            else:
                if n.op == "add":
                    x = x + env[n.inputs[1]]
                else:
                    w = jax.device_put(params[n.name]["w"], cpu)
                    b = jax.device_put(params[n.name]["b"], cpu)
                    if n.op == "fc":
                        x = x.reshape(x.shape[0], -1) @ w + b
                    else:
                        lo, hi = n.pad
                        x = jax.lax.conv_general_dilated(
                            x, w, (n.stride, n.stride), ((lo, hi), (lo, hi)),
                            dimension_numbers=("NHWC", "HWIO", "NHWC"),
                            feature_group_count=n.groups) + b
                if n.relu:
                    x = jax.nn.relu(x)
                if amax is not None:
                    amax[n.name] = float(jnp.max(jnp.abs(x)))
            env[n.name] = x
    return np.asarray(env[net.output])


def calibrate(net: Net, params: dict, calib: np.ndarray) -> dict:
    """Float forward over the calibration frame on the host CPU: the
    largest magnitude of the input and of each conv, fc and add output
    (after its ReLU)."""
    amax = {}
    float_forward(net, params, calib, amax)
    return amax


def add_format(e_a: int, e_b: int, e_out: int, name: str = "add"
               ) -> tuple[tuple[int, int], int]:
    """-> ((left shift of a, of b), requantize shift) of an add whose
    int8 operands have exponents ``e_a`` and ``e_b`` and whose output has
    ``e_out``: both go to the finer exponent, the sum to ``e_out``."""
    fine = min(e_a, e_b)
    align = (e_a - fine, e_b - fine)
    if max(align) > MAX_ALIGN:
        raise ValueError(f"node {name!r}: operand exponents {e_a} and {e_b} "
                         f"lie more than {MAX_ALIGN} bits apart")
    return align, int(np.clip(e_out - fine, -31, 31))


@dataclasses.dataclass
class Layer:
    node: Node
    wq: np.ndarray | None = None     # int8 weights (values within weight_bits)
    bias: np.ndarray | None = None   # int32 bias on the accumulator's scale
    shift: np.ndarray | None = None  # int32 per output channel (add: one)
    acc_e: np.ndarray | None = None  # accumulator exponent per channel
    align: tuple[int, int] = (0, 0)  # add: left shifts of its operands
    last: bool = False


@dataclasses.dataclass
class Network:
    e_input: int
    layers: list[Layer]

    @property
    def out_scale(self) -> np.ndarray:
        """float32 value of one unit of the last accumulators."""
        last = [lyr for lyr in self.layers if lyr.last][0]
        return np.exp2(last.acc_e.astype(np.float32))


def quantize(net: Net, params: dict, amax: dict, *,
             weight_bits: int = 8) -> Network:
    """Freeze every format and quantize the weights (host CPU, float32
    as the formats are defined)."""
    cpu = jax.devices("cpu")[0]
    e = {INPUT: _exponent(amax["__input__"], ACT_BITS)}
    wmax = 2 ** (weight_bits - 1) - 1
    layers = []
    for n in net.nodes:
        e_in = e[n.inputs[0]]
        if n.op == "add":
            e_out = _exponent(amax[n.name], ACT_BITS)
            align, shift = add_format(e_in, e[n.inputs[1]], e_out, n.name)
            layers.append(Layer(node=n, shift=np.int32(shift), align=align))
            e[n.name] = e_out
            continue
        if n.op not in graph.COMPUTE:
            layers.append(Layer(node=n))
            e[n.name] = e_in
            continue
        e_out = _exponent(amax[n.name], ACT_BITS)
        with jax.default_device(cpu):
            w = jax.device_put(jnp.asarray(params[n.name]["w"], jnp.float32),
                               cpu)
            wabs = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)))
            e_w = np.asarray(jnp.ceil(jnp.log2(jnp.maximum(wabs, 1e-12)
                                               / wmax)), np.int64)
        b = np.asarray(params[n.name]["b"], np.float64)
        b_exp = np.full(b.shape, -(10 ** 9), np.int64)
        nz = np.abs(b) > 0
        b_exp[nz] = np.ceil(np.log2(np.abs(b[nz])))
        e_w = np.maximum(e_w, np.maximum(b_exp - 30, e_out - 31) - e_in)
        with jax.default_device(cpu):
            scale = jnp.asarray(np.ldexp(np.float32(1), -e_w).astype(np.float32))
            wq = np.asarray(jnp.clip(jnp.round(w * scale), -wmax - 1, wmax)
                            .astype(jnp.int8))
        acc_e = e_in + e_w
        bias = np.clip(np.round(b / np.exp2(acc_e.astype(np.float64))),
                       -2 ** 31, 2 ** 31 - 1).astype(np.int32)
        shift = np.clip(e_out - acc_e, -31, 31).astype(np.int32)
        layers.append(Layer(node=n, wq=wq, bias=bias, shift=shift,
                            acc_e=acc_e, last=n.name == net.output))
        e[n.name] = e_out
    return Network(e_input=e[INPUT], layers=layers)


def build(cfg: dict, params: dict, calib: np.ndarray, *,
          weight_bits: int = 8) -> Network:
    net = graph.parse(cfg)
    return quantize(net, params, calibrate(net, params, calib),
                    weight_bits=weight_bits)


def quantize_input(net: Network, frames: np.ndarray) -> np.ndarray:
    q = np.rint(np.asarray(frames, np.float32)
                * np.float32(2.0 ** -net.e_input))
    return np.clip(q, -128, 127).astype(np.int8)


def _requantize(acc, shift):
    """int32 accumulators -> int8: floor right shift, or a left shift
    that saturates (any nonzero value shifted left by 8 is past the
    int8 rails, so clipping to +-256 first cannot change the result)."""
    right = acc >> jnp.minimum(jnp.maximum(shift, 0), 31)
    left = jnp.clip(acc, -256, 256) << jnp.minimum(jnp.maximum(-shift, 0), 8)
    return jnp.clip(jnp.where(shift >= 0, right, left), -128, 127
                    ).astype(jnp.int8)


def add(a, b, align: tuple[int, int], shift, relu: bool):
    """Two int8 tensors summed exactly at the finer exponent (``align``:
    each operand's left shift), ReLU where folded, requantized by
    ``shift`` to int8."""
    s = (a.astype(jnp.int32) << align[0]) + (b.astype(jnp.int32) << align[1])
    if relu:
        s = jnp.maximum(s, 0)
    return _requantize(s, jnp.int32(shift))


def avgpool(xq, n: Node):
    """int8 average over each window, the padding counted as zeros: the
    int32 sum over the window's size, rounded half up."""
    size = n.kernel * n.kernel
    s = _window(xq.astype(jnp.int32), jnp.int32(0), jax.lax.add, n)
    return ((s + size // 2) // size).astype(jnp.int8)


def _forward(xq, weights, net: Network):
    env = {INPUT: xq}
    for lyr, w in zip(net.layers, weights):
        n = lyr.node
        x = env[n.inputs[0]]
        if n.op == "maxpool":
            env[n.name] = _window(x, jnp.int8(-128), jax.lax.max, n)
        elif n.op == "avgpool":
            env[n.name] = avgpool(x, n)
        elif n.op == "relu":
            env[n.name] = jnp.maximum(x, jnp.int8(0))
        elif n.op == "flatten":
            env[n.name] = x.reshape(x.shape[0], -1)
        elif n.op == "add":
            env[n.name] = add(x, env[n.inputs[1]], lyr.align, lyr.shift,
                              n.relu)
        else:
            wq, bias, shift = w
            if n.op == "fc":
                acc = jax.lax.dot_general(
                    x.reshape(x.shape[0], -1), wq, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
            else:
                # A grouped conv is one plain conv per group: input
                # channels [k*C/G, (k+1)*C/G) meet output channels
                # [k*M/G, (k+1)*M/G).
                lo, hi = n.pad
                groups, cg, mg = n.groups, wq.shape[2], wq.shape[3] // n.groups
                acc = jnp.concatenate([jax.lax.conv_general_dilated(
                    x[..., k * cg:(k + 1) * cg], wq[..., k * mg:(k + 1) * mg],
                    (n.stride, n.stride), ((lo, hi), (lo, hi)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    preferred_element_type=jnp.int32) for k in range(groups)],
                    axis=-1)
            acc = acc + bias
            if lyr.last:
                return acc
            env[n.name] = _requantize(jnp.maximum(acc, 0) if n.relu else acc,
                                      shift)
    raise ValueError("network has no output node")


def accumulators(net: Network, frames: np.ndarray, *, block: int = 8,
                 device=None) -> np.ndarray:
    """The output node's int32 accumulators for ``frames``, computed
    ``block`` frames at a time on ``device`` (default: JAX's)."""
    device = device or jax.devices()[0]
    weights = jax.device_put(
        [None if lyr.wq is None else (lyr.wq, lyr.bias, lyr.shift)
         for lyr in net.layers], device)
    fn = jax.jit(lambda xq, w: _forward(xq, w, net))
    xq = quantize_input(net, frames)
    n = len(xq)
    pad = (-n) % block
    if pad:
        xq = np.concatenate([xq, np.zeros((pad,) + xq.shape[1:], xq.dtype)])
    out = [np.asarray(fn(jax.device_put(xq[i:i + block], device), weights))
           for i in range(0, len(xq), block)]
    return np.concatenate(out)[:n]


def logits(net: Network, frames: np.ndarray, **kw) -> np.ndarray:
    acc = accumulators(net, frames, **kw)
    return acc.astype(np.float32) * net.out_scale.reshape(
        (1,) * (acc.ndim - 1) + (-1,))
