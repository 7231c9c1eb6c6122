"""Channel-wise fixed-point quantization (paper Section 3.3, Fig. 3(c)).

The paper computes int8/int16 MACs into 32-bit partial sums; different
channels may use different fixed-point formats (power-of-2 scales = "shift
bits"), aligned by left-shifters before accumulation, then right-shifted and
truncated when writing output activations. We reproduce exactly that
arithmetic so the Pallas conv kernel and the pure-jnp oracle agree bit-for-bit
with the hardware-style pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def po2_scale(x: jnp.ndarray, axis, bits: int = 8) -> jnp.ndarray:
    """Per-channel power-of-2 exponent e such that x / 2^e fits int<bits>.

    Returns integer exponents (can be negative). Reduction over all axes
    except `axis`.
    """
    qmax = 2 ** (bits - 1) - 1
    red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
    amax = jnp.max(jnp.abs(x), axis=red, keepdims=False)
    amax = jnp.maximum(amax, 1e-12)
    # smallest e with amax / 2^e <= qmax
    e = jnp.ceil(jnp.log2(amax / qmax)).astype(jnp.int32)
    return e


def po2_exponent(amax: float, bits: int = 8) -> int:
    """Smallest integer e with ``amax / 2^e <= qmax`` — the frozen
    per-tensor activation format a calibration pass records."""
    import math
    qmax = 2 ** (bits - 1) - 1
    return math.ceil(math.log2(max(float(amax), 1e-12) / qmax))


def quantize_to_exponent(x: jnp.ndarray, e: int, bits: int = 8):
    """Quantize onto a *given* po2 format (compile-time frozen scale):
    ``q = clip(round(x / 2^e))`` as int8/int16."""
    qmax = 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(x * (2.0 ** (-e))), -qmax - 1, qmax)
    return q.astype(jnp.int8 if bits <= 8 else jnp.int16)


def quantize_to_exponent_np(x, e: int, bits: int = 8):
    """Numpy twin of :func:`quantize_to_exponent`, whole array at once:
    the plain reference :func:`quantize_frames_np` is held to.
    Bit-identical: same float32 multiply, same round-half-to-even, same
    clip
    (``tests/test_executor.py::test_quantize_np_twin_bit_identical``
    pins the equivalence)."""
    import numpy as np
    qmax = 2 ** (bits - 1) - 1
    q = np.clip(np.rint(np.asarray(x, np.float32) * np.float32(2.0 ** (-e))),
                -qmax - 1, qmax)
    return q.astype(np.int8 if bits <= 8 else np.int16)


def copy_frames_np(frames):
    """Copy float frames once into a fresh float32 scratch laid out in
    the frames' own memory order, batch outermost: the one host pass of
    the served quantize-in.

    ``frames`` is a sequence of ``[H, W, C]`` frames or an
    ``[N, H, W, C]`` array; the memory order is the first frame's (the
    array's). Returns ``(scratch, order)``: ``scratch`` is the
    C-contiguous ``[N] + [shape[a] for a in order]`` buffer, ``order``
    the frame axes outermost first in memory, so
    ``scratch.transpose(frame_axes(order))`` is the ``[N, H, W, C]``
    stack. Each frame is converted as ``np.asarray(x, np.float32)``
    converts it. Keeping the frames' order lets strided frames be read
    in order (a copy into C order costs as much as the whole quantize).
    The scratch belongs to this call alone."""
    import numpy as np
    n = len(frames)
    if isinstance(frames, np.ndarray):
        shape, strides = frames.shape[1:], frames.strides[1:]
    elif n:
        first = np.asarray(frames[0])
        shape, strides = first.shape, first.strides
    else:
        raise ValueError("no frames to quantize")
    order = tuple(sorted(range(len(shape)), key=lambda a: -abs(strides[a])))
    scratch = np.empty((n,) + tuple(shape[a] for a in order), np.float32)
    stack = scratch.transpose(frame_axes(order))
    for i in range(n):
        if np.shape(frames[i]) != shape:
            raise ValueError(f"frame {i} has shape {np.shape(frames[i])}, "
                             f"frame 0 {shape}")
        stack[i] = frames[i]
    return scratch, order


def frame_axes(order) -> tuple[int, ...]:
    """The transpose that takes a batch laid out with its frame axes in
    ``order`` (batch outermost, :func:`copy_frames_np`) back to
    ``[N, H, W, C]``."""
    return (0,) + tuple(1 + order.index(a) for a in range(len(order)))


def quantize_frames_np(frames, e: int, bits: int = 8):
    """Quantize float frames straight into a fresh ``[N, H, W, C]``
    int8 batch (int16 for ``bits`` 16) on the host: the plain reference
    the served quantize-in (``CompiledRunner.quantize``, a device
    program) is held to.

    ``frames`` is a sequence of ``[H, W, C]`` frames or an
    ``[N, H, W, C]`` array. The result equals
    :func:`quantize_to_exponent_np` of the stacked frames: the frames are
    copied once into a float32 scratch (:func:`copy_frames_np`), which
    is scaled, clamped to the rails and rounded half to even in place,
    the rounding casting into the result. Clamping before ``rint``
    equals clipping after it, since the rails are integers and ``rint``
    is monotone (``tests/test_executor.py`` pins the equivalence). Both
    arrays keep the frames' own memory order (batch outermost, as
    ``np.stack`` lays it out)."""
    import numpy as np
    scratch, order = copy_frames_np(frames)
    qmax = 2 ** (bits - 1) - 1
    out = np.empty(scratch.shape, np.int8 if bits <= 8 else np.int16)
    np.multiply(scratch, np.float32(2.0 ** (-e)), out=scratch)
    np.clip(scratch, -qmax - 1, qmax, out=scratch)
    np.rint(scratch, out=out, casting="unsafe")
    return out.transpose(frame_axes(order))


def quantize_po2(x: jnp.ndarray, axis: int, bits: int = 8):
    """-> (q int8/int16, e int32 per-channel): x ~= q * 2^e."""
    e = po2_scale(x, axis, bits)
    shape = [1] * x.ndim
    shape[axis % x.ndim] = -1
    scale = jnp.exp2(-e.astype(jnp.float32)).reshape(shape)
    qmax = 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(x * scale), -qmax - 1, qmax)
    dt = jnp.int8 if bits <= 8 else jnp.int16
    return q.astype(dt), e


def dequantize_po2(q: jnp.ndarray, e: jnp.ndarray, axis: int) -> jnp.ndarray:
    shape = [1] * q.ndim
    shape[axis % q.ndim] = -1
    return q.astype(jnp.float32) * jnp.exp2(e.astype(jnp.float32)).reshape(shape)


def align_partial_sums(psum: jnp.ndarray, e_in: jnp.ndarray,
                       e_common: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Left-shift partial sums of per-channel formats onto a common scale
    (the adder-tree alignment in Fig. 3(c)). int32 in, int32 out."""
    shape = [1] * psum.ndim
    shape[axis % psum.ndim] = -1
    sh = (e_in - e_common).reshape(shape)
    return jnp.left_shift(psum, jnp.maximum(sh, 0)) >> jnp.maximum(-sh, 0)


def saturating_signed_shift(acc32: jnp.ndarray,
                            shift: jnp.ndarray) -> jnp.ndarray:
    """``acc >> shift`` with truncation for ``shift >= 0`` and a
    *saturating* left shift for ``shift < 0`` — no int32 wraparound, so a
    downstream clip onto int8/int16 rails sees the true sign.

    The left-shift amount is capped at 16: every nonzero value shifted
    left 16 already exceeds the int16 (a fortiori int8) rails, so the cap
    is bit-neutral for any consumer clipping to <= 16-bit outputs, and it
    keeps the preimage clamp nondegenerate (at a full 31-bit shift the
    clamp bound collapses to 0 and would zero positive values). Plain jnp
    ops — shared by :func:`requantize_output` and the Pallas GEMM epilogue
    (`kernels/conv2d_int8/kernel.py`)."""
    sh = jnp.asarray(shift, jnp.int32)
    sl = jnp.minimum(jnp.maximum(-sh, 0), 16)
    lo32 = jnp.right_shift(jnp.iinfo(jnp.int32).min, sl)
    hi32 = jnp.right_shift(jnp.iinfo(jnp.int32).max, sl)
    return jnp.where(sh >= 0,
                     jnp.right_shift(acc32, jnp.minimum(sh, 31)),
                     jnp.left_shift(jnp.clip(acc32, lo32, hi32), sl))


def requantize_output(acc32: jnp.ndarray, e_acc: jnp.ndarray | int,
                      e_out: jnp.ndarray | int, bits: int = 8) -> jnp.ndarray:
    """Right-shift + truncate 32-bit accumulators to the output activation
    format (paper: "partial sums should be right shifted and truncated")."""
    y = saturating_signed_shift(acc32, jnp.asarray(e_out - e_acc, jnp.int32))
    qmax = 2 ** (bits - 1) - 1
    dt = jnp.int8 if bits <= 8 else jnp.int16
    return jnp.clip(y, -qmax - 1, qmax).astype(dt)
