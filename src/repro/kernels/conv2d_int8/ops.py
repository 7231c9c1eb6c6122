"""Jitted wrappers: conv2d / fc as int8 im2col + the int8 GEMM Pallas
kernel with the fused bias/ReLU/requantize epilogue.

The im2col (the line-buffer address generator) runs in XLA as pure int8
slicing — no float32 patch materialization; the MAC array + output
pipeline is the Pallas kernel. Grouped convolutions (e.g. AlexNet's
two-tower layers) run one weight-stationary GEMM per group, exactly like
the paper's per-engine channel split.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.conv2d_int8.kernel import gemm_int8, padded_k
from repro.kernels.conv2d_int8.ref import conv2d_int8_via


@partial(jax.jit, static_argnames=("stride", "padding", "groups", "relu",
                                   "interpret", "emit_int32"))
def conv2d_int8(x: jnp.ndarray, w: jnp.ndarray, shift: jnp.ndarray,
                bias: jnp.ndarray | None = None, *, stride: int = 1,
                padding="same", groups: int = 1, relu: bool = False,
                interpret: bool = False,
                emit_int32: bool = False) -> jnp.ndarray:
    """x [B,H,W,C] int8, w [R,S,C/groups,M] int8, shift/bias [M] int32 ->
    int8 [B,Ho,Wo,M] (int32 with ``emit_int32``).

    ``padding`` is "same" or an explicit ((top, bottom), (left, right));
    ``stride`` and ``groups`` are arbitrary, so every conv shape in the
    paper's four models (stride-4/stride-2 stems, grouped towers) takes
    this route.
    """
    return conv2d_int8_via(gemm_int8, x, w, shift, bias, stride=stride,
                           padding=padding, groups=groups, relu=relu,
                           pad_k=padded_k, interpret=interpret,
                           emit_int32=emit_int32)


@partial(jax.jit, static_argnames=("relu", "interpret", "emit_int32"))
def fc_int8(x: jnp.ndarray, w: jnp.ndarray, shift: jnp.ndarray,
            bias: jnp.ndarray | None = None, *, relu: bool = False,
            interpret: bool = False,
            emit_int32: bool = False) -> jnp.ndarray:
    """Fully-connected layer on the same GEMM engine: x [B,F] int8,
    w [F,M] int8 -> int8 [B,M] (int32 with ``emit_int32``)."""
    return gemm_int8(x, w, shift, bias, relu=relu, interpret=interpret,
                     emit_int32=emit_int32)
