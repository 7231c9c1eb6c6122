"""Pallas TPU kernel: chunked diagonal linear recurrence.

h_t = a_t * h_{t-1} + b_t  over [B, S, D], computed in sequence chunks:
within a chunk the recurrence is expanded with a log-depth (Blelloch-style)
pass over VMEM-resident tiles; the carry h crosses chunks in a VMEM scratch
that persists across the sequential grid dimension. This is the TPU-native
replacement for the FPGA's per-row systolic update — long_500k decodes and
32k prefills of the SSM/hybrid archs are bound by this op.

Grid: (B_tiles, n_chunks) — the chunk dim is sequential ("arbitrary"
semantics), the batch dim parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, h_ref, *, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]           # [bt, chunk, d]
    b = b_ref[...]

    # In-chunk associative scan (log depth), fp32.
    def op(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    aa, bb = jax.lax.associative_scan(op, (a, b), axis=1)
    # Fold in the inter-chunk carry: h_t = aa_t * h_in + bb_t.
    h_in = h_ref[...]
    h = aa * h_in[:, None, :] + bb
    o_ref[...] = h.astype(o_ref.dtype)
    h_ref[...] = h[:, -1, :]


def linear_scan(a: jnp.ndarray, b: jnp.ndarray, *, chunk: int = 256,
                bt: int = 8, interpret: bool = False) -> jnp.ndarray:
    """a, b [B,S,D] -> h [B,S,D] (fp32 recurrence, output dtype of b)."""
    B, S, D = a.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    bt = max(1, min(bt, B))
    while B % bt:
        bt -= 1
    grid = (B // bt, S // chunk)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((bt, chunk, D), lambda i, c: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((bt, chunk, D), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), b.dtype),
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
