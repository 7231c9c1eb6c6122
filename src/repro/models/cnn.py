"""CNN substrate in JAX: the paper's four benchmark models (VGG16, AlexNet,
ZF, YOLO) as runnable networks, in float and in the paper's channel-wise
fixed-point arithmetic (int8/int16 MACs, 32-bit accumulation, shift-aligned
per-channel formats).

The layer graph comes from ``repro.core.workload`` and execution is owned by
``repro.core.program`` (single source of truth for the allocator, the
simulator, and the runnable model): ``forward(quantized=True)`` is a thin
wrapper that compiles an :class:`~repro.core.program.EngineProgram` —
freezing po2 scales on the given batch — and runs it, so the fixed-point
pipeline here is byte-for-byte the one the benchmarks cycle-count. NHWC
layout.
"""

from __future__ import annotations

import math
import zlib
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.program import compile_model, float_forward, host_device
from repro.core.workload import CNNModel

Params = dict[str, Any]


def init_params(model: CNNModel, key=None, dtype=jnp.float32) -> Params:
    """Seeded random weights, drawn on the host CPU device so the same
    key gives the same floats on every platform."""
    with jax.default_device(host_device()):
        return _init_params(model, key, dtype)


def _init_params(model: CNNModel, key, dtype) -> Params:
    key = key if key is not None else jax.random.PRNGKey(0)
    p: Params = {}
    hw = model.input_hw
    for lyr in model.layers:
        if lyr.kind == "pool":
            hw = lyr.out_hw(hw)
            continue
        # stable per-layer fold (builtin str hash is salted per process,
        # which made init non-reproducible across runs)
        k = jax.random.fold_in(key, zlib.crc32(lyr.name.encode()) % (2 ** 31))
        if lyr.kind == "fc":
            fan_in = lyr.in_ch
            w = jax.random.normal(k, (lyr.in_ch, lyr.out_ch), jnp.float32)
        else:
            fan_in = lyr.kernel * lyr.kernel * lyr.in_ch // lyr.groups
            w = jax.random.normal(
                k, (lyr.kernel, lyr.kernel, lyr.in_ch // lyr.groups,
                    lyr.out_ch), jnp.float32)
        p[lyr.name] = {"w": (w / math.sqrt(fan_in)).astype(dtype),
                       "b": jnp.zeros((lyr.out_ch,), dtype)}
        hw = lyr.out_hw(hw)
    return p


def forward(params: Params, model: CNNModel, x: jnp.ndarray,
            quantized: bool = False, bits: int = 8,
            use_kernel: bool = False) -> jnp.ndarray:
    """x [B,H,W,C] float. quantized=True compiles an EngineProgram with
    scales calibrated on ``x`` and runs the paper's fixed-point pipeline
    (per-channel po2 weight formats, int32 accumulation, fused
    bias/ReLU/shift epilogue, int8 activations end-to-end).
    use_kernel=True routes the MACs through the Pallas PE-array kernel
    (interpret mode on CPU; the real thing on TPU).

    Note: this wrapper recompiles (and recalibrates on ``x``) every call —
    the seed's dynamic-scale semantics. For repeated inference, compile
    once with ``repro.core.program.compile_model`` and reuse the program."""
    if not quantized:
        return float_forward(params, model, x)
    prog = compile_model(model, params, bits=bits, calib_batch=x)
    # No silent fallback: run() raises up front if the kernel route is
    # requested but unavailable (bits=16 / Pallas missing).
    return prog.run(x, use_kernel=use_kernel)
