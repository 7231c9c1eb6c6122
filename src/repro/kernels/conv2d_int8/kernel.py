"""Pallas TPU kernel: weight-stationary int8 implicit-GEMM conv engine.

Hardware mapping of the paper's PE array (DESIGN.md §2):
  * the M' x C' x R x S multiplier grid  ->  one MXU tile pair
    (bk x bm int8 GEMM tile, int32 accumulate);
  * K-row groups                        ->  the bn tile of im2col rows;
  * weight-stationary reuse             ->  w block revisited across the
    n-grid (Pallas keeps it in VMEM; index_map pins the same block);
  * bias add + ReLU + per-channel shift ->  the epilogue on the last
    k-step (Fig. 3(c)) — the full requantize pipeline is fused, so
    activations leave the engine already in int8.

Grid: (n_tiles, m_tiles, k_tiles) with k innermost (sequential,
accumulating into an int32 VMEM scratch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant


def _kernel(x_ref, w_ref, bias_ref, shift_ref, o_ref, acc_ref, *, n_k: int,
            relu: bool = False, emit_int32: bool = False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 tiles straight into the MXU's int8 path, int32 accumulate
    # (the MXU has no int32 x int32 matmul).
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        # The paper's output stage, fused: 32-bit partial sums + bias, ReLU,
        # per-output-channel shift onto the activation format, truncate.
        acc = acc_ref[...] + bias_ref[...]            # [1, bm] broadcast
        if relu:
            acc = jnp.maximum(acc, 0)
        if emit_int32:
            # Raw 32-bit partial sums (the psumSpad view, pre-requantize).
            o_ref[...] = acc
        else:
            sh = shift_ref[...]                          # [1, bm]
            # shift >= 0: right-shift + truncate; shift < 0: the left-shift
            # branch of the Fig. 3(c) aligner (output format finer than the
            # accumulator's), saturating instead of wrapping int32.
            y = quant.saturating_signed_shift(acc, sh)
            o_ref[...] = jnp.clip(y, -128, 127).astype(jnp.int8)


def gemm_int8(x: jnp.ndarray, w: jnp.ndarray, shift: jnp.ndarray,
              bias: jnp.ndarray | None = None, *, relu: bool = False,
              bn: int = 256, bm: int = 256, bk: int = 256,
              interpret: bool = False,
              emit_int32: bool = False) -> jnp.ndarray:
    """int8 GEMM with fused requantize epilogue: [N,K]x[K,M] -> int8 [N,M].

    ``out = clip((relu?)(x @ w + bias) >> shift)`` with per-column (output
    channel) ``shift``/``bias``; negative shifts left-shift. With
    ``emit_int32`` the epilogue stops after bias/ReLU and returns the raw
    int32 accumulators.

    Block sizes are MXU-aligned (multiples of 128 for the lane dim, 32 for
    int8 sublanes). N/K/M are padded to the block grid.
    """
    N, K = x.shape
    K2, M = w.shape
    assert K == K2, (x.shape, w.shape)
    if bias is None:
        bias = jnp.zeros((M,), jnp.int32)
    bn_, bm_, bk_ = min(bn, _rnd(N)), min(bm, _rnd(M)), min(bk, _rnd(K))
    Np, Mp, Kp = _pad(N, bn_), _pad(M, bm_), padded_k(K, bk)
    xp = jnp.pad(x, ((0, Np - N), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Mp - M)))
    # Bias and shift ride as [1, Mp] rows: a 1-D block's lane tiling
    # does not match Mosaic's layout for it.
    bp = jnp.pad(bias.astype(jnp.int32), (0, Mp - M)).reshape(1, Mp)
    sp = jnp.pad(shift.astype(jnp.int32), (0, Mp - M)).reshape(1, Mp)
    n_k = Kp // bk_
    grid = (Np // bn_, Mp // bm_, n_k)
    out_dt = jnp.int32 if emit_int32 else jnp.int8
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, relu=relu,
                          emit_int32=emit_int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn_, bk_), lambda n, m, k: (n, k)),
            pl.BlockSpec((bk_, bm_), lambda n, m, k: (k, m)),
            pl.BlockSpec((1, bm_), lambda n, m, k: (0, m)),
            pl.BlockSpec((1, bm_), lambda n, m, k: (0, m)),
        ],
        out_specs=pl.BlockSpec((bn_, bm_), lambda n, m, k: (n, m)),
        out_shape=jax.ShapeDtypeStruct((Np, Mp), out_dt),
        scratch_shapes=[pltpu.VMEM((bn_, bm_), jnp.int32)],
        interpret=interpret,
        name="gemm_int8",
    )(xp, wp, bp, sp)
    return out[:N, :M]


def padded_k(K: int, bk: int = 256) -> int:
    """The reduction length :func:`gemm_int8` pads ``K`` to."""
    return _pad(K, min(bk, _rnd(K)))


def _rnd(n: int, to: int = 128) -> int:
    return max(to, (n + to - 1) // to * to)


def _pad(n: int, b: int) -> int:
    return (n + b - 1) // b * b
