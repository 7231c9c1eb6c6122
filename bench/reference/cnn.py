"""Plain reference of a served int8 CNN, written from its definition.

It imports nothing of the program under test. From the float weights,
biases and calibration frame that the benchmark drew from its seed, it
freezes the same fixed-point formats the configuration states and runs
the integer forward pass:

* activations: one power-of-two exponent per tensor,
  ``e = ceil(log2(amax / qmax))`` over the calibration frame (the input,
  and each layer's output after ReLU);
* weights: one power-of-two exponent per output channel, floored so the
  bias fits the 32-bit accumulator and the output shift stays within 31
  bits, then ``round(w / 2^e)`` clipped to int8;
* each compute layer: int8 x int8 products summed exactly in int32, plus
  the bias on the accumulator's scale, ReLU, then an arithmetic shift by
  ``e_out - (e_in + e_w)`` (floor for a right shift, saturating for a
  left one) clipped to int8; max pooling on the integers;
* the last layer keeps its int32 accumulators; logits are those times
  ``2^(e_in + e_w)``, per channel, in float32.

Calibration and quantization run on the host CPU with float32 arithmetic
(the float forward op by op, so its rounding is the host's). The integer
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

ACT_BITS = 8


def geometry(layers: list[dict], input_hw: int) -> list[dict]:
    """Each layer's input and output size and its (lo, hi) spatial
    padding: the output is ``out_size`` where given, else the input over
    the stride (1 for a fully connected layer); the padding is what that
    output needs, split with the odd pixel at the end."""
    out, hw = [], input_hw
    for lyr in layers:
        stride = lyr.get("stride", 1)
        if lyr["kind"] == "fc":
            o = 1
        else:
            o = lyr.get("out_size") or hw // stride
        need = max((o - 1) * stride + lyr["kernel"] - hw, 0)
        out.append(dict(lyr, stride=stride, groups=lyr.get("groups", 1),
                        in_hw=hw, out_hw=o, pad=(need // 2, need - need // 2)))
        hw = o
    return out


def _exponent(amax: float, bits: int) -> int:
    qmax = 2 ** (bits - 1) - 1
    return math.ceil(math.log2(max(float(amax), 1e-12) / qmax))


def calibrate(geo: list[dict], params: dict, calib: np.ndarray) -> dict:
    """Float forward over the calibration frame on the host CPU: the
    largest magnitude of the input and of each compute layer's output
    (after ReLU on hidden layers)."""
    cpu = jax.devices("cpu")[0]
    compute = [g for g in geo if g["kind"] != "pool"]
    amax = {}
    with jax.default_device(cpu):
        x = jax.device_put(jnp.asarray(calib, jnp.float32), cpu)
        amax["__input__"] = float(jnp.max(jnp.abs(x)))
        for g in geo:
            lo, hi = g["pad"]
            if g["kind"] == "pool":
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, (1, g["kernel"], g["kernel"], 1),
                    (1, g["stride"], g["stride"], 1),
                    ((0, 0), (lo, hi), (lo, hi), (0, 0)))
                continue
            w = jax.device_put(params[g["name"]]["w"], cpu)
            b = jax.device_put(params[g["name"]]["b"], cpu)
            if g["kind"] == "fc":
                x = x.reshape(x.shape[0], -1) @ w + b
            else:
                x = jax.lax.conv_general_dilated(
                    x, w, (g["stride"], g["stride"]), ((lo, hi), (lo, hi)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    feature_group_count=g["groups"]) + b
            if g is not compute[-1]:
                x = jax.nn.relu(x)
            amax[g["name"]] = float(jnp.max(jnp.abs(x)))
    return amax


@dataclasses.dataclass
class Layer:
    geo: dict
    wq: np.ndarray | None = None     # int8 weights (values within weight_bits)
    bias: np.ndarray | None = None   # int32 bias on the accumulator's scale
    shift: np.ndarray | None = None  # int32 per output channel
    acc_e: np.ndarray | None = None  # accumulator exponent per channel
    last: bool = False


@dataclasses.dataclass
class Network:
    e_input: int
    layers: list[Layer]

    @property
    def out_scale(self) -> np.ndarray:
        """float32 value of one unit of the last accumulators."""
        last = [lyr for lyr in self.layers if lyr.wq is not None][-1]
        return np.exp2(last.acc_e.astype(np.float32))


def quantize(geo: list[dict], params: dict, amax: dict, *,
             weight_bits: int = 8) -> Network:
    """Freeze every format and quantize the weights (host CPU, float32
    as the formats are defined)."""
    cpu = jax.devices("cpu")[0]
    e_act = _exponent(amax["__input__"], ACT_BITS)
    e_input = e_act
    compute = [g for g in geo if g["kind"] != "pool"]
    wmax = 2 ** (weight_bits - 1) - 1
    layers = []
    for g in geo:
        if g["kind"] == "pool":
            layers.append(Layer(geo=g))
            continue
        last = g is compute[-1]
        e_out = _exponent(amax[g["name"]], ACT_BITS)
        with jax.default_device(cpu):
            w = jax.device_put(jnp.asarray(params[g["name"]]["w"], jnp.float32),
                               cpu)
            wabs = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)))
            e_w = np.asarray(jnp.ceil(jnp.log2(jnp.maximum(wabs, 1e-12)
                                               / wmax)), np.int64)
        b = np.asarray(params[g["name"]]["b"], np.float64)
        b_exp = np.full(b.shape, -(10 ** 9), np.int64)
        nz = np.abs(b) > 0
        b_exp[nz] = np.ceil(np.log2(np.abs(b[nz])))
        e_w = np.maximum(e_w, np.maximum(b_exp - 30, e_out - 31) - e_act)
        with jax.default_device(cpu):
            scale = jnp.asarray(np.ldexp(np.float32(1), -e_w).astype(np.float32))
            wq = np.asarray(jnp.clip(jnp.round(w * scale), -wmax - 1, wmax)
                            .astype(jnp.int8))
        acc_e = e_act + e_w
        bias = np.clip(np.round(b / np.exp2(acc_e.astype(np.float64))),
                       -2 ** 31, 2 ** 31 - 1).astype(np.int32)
        shift = np.clip(e_out - acc_e, -31, 31).astype(np.int32)
        layers.append(Layer(geo=g, wq=wq, bias=bias, shift=shift,
                            acc_e=acc_e, last=last))
        e_act = e_out
    return Network(e_input=e_input, layers=layers)


def build(cfg: dict, params: dict, calib: np.ndarray, *,
          weight_bits: int = 8) -> Network:
    geo = geometry(cfg["layers"], cfg["input_hw"])
    return quantize(geo, params, calibrate(geo, params, calib),
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


def _forward(xq, weights, net: Network):
    for lyr, w in zip(net.layers, weights):
        g = lyr.geo
        lo, hi = g["pad"]
        if lyr.wq is None:
            xq = jax.lax.reduce_window(
                xq, jnp.int8(-128), jax.lax.max,
                (1, g["kernel"], g["kernel"], 1),
                (1, g["stride"], g["stride"], 1),
                ((0, 0), (lo, hi), (lo, hi), (0, 0)))
            continue
        wq, bias, shift = w
        if g["kind"] == "fc":
            acc = jax.lax.dot_general(
                xq.reshape(xq.shape[0], -1), wq, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        else:
            # A grouped conv is one plain conv per group: input channels
            # [k*C/G, (k+1)*C/G) meet output channels [k*M/G, (k+1)*M/G).
            groups, cg, mg = g["groups"], wq.shape[2], wq.shape[3] // g["groups"]
            acc = jnp.concatenate([jax.lax.conv_general_dilated(
                xq[..., k * cg:(k + 1) * cg], wq[..., k * mg:(k + 1) * mg],
                (g["stride"], g["stride"]), ((lo, hi), (lo, hi)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.int32) for k in range(groups)],
                axis=-1)
        acc = acc + bias
        if lyr.last:
            return acc
        xq = _requantize(jnp.maximum(acc, 0), shift)
    raise ValueError("network has no compute layer")


def accumulators(net: Network, frames: np.ndarray, *, block: int = 8,
                 device=None) -> np.ndarray:
    """The last layer's int32 accumulators for ``frames``, computed
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
