"""Pallas TPU kernel: blockwise (flash) causal attention, forward.

Grid: (batch*heads, q_tiles, kv_tiles); kv innermost sequential with the
online-softmax running max / denominator / accumulator in VMEM scratch.
Tiles are MXU-aligned (q/kv block 128+). Causal tiles fully above the
diagonal are masked out (compute-skipping for them is the `block_causal`
hillclimb variant in EXPERIMENTS.md §Perf; the baseline computes+masks).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, n_kv: int, bq: int, bkv: int, scale: float, causal: bool,
            window: int, skv: int, sq: int):
    kv = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # [bq, d]
    k = k_ref[0]                       # [bkv, d]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [bq, bkv]
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) \
        + (skv - sq)
    kpos = kv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -1e30)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(-1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv == n_kv - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 256, bkv: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """q [B,Sq,H,d], k/v [B,Skv,H,d] -> [B,Sq,H,d]. O(Sq*bkv) memory."""
    B, Sq, H, d = q.shape
    Skv = k.shape[1]
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0
    qf = q.swapaxes(1, 2).reshape(B * H, Sq, d)
    kf = k.swapaxes(1, 2).reshape(B * H, Skv, d)
    vf = v.swapaxes(1, 2).reshape(B * H, Skv, d)
    grid = (B * H, Sq // bq, Skv // bkv)
    out = pl.pallas_call(
        functools.partial(
            _kernel, n_kv=Skv // bkv, bq=bq, bkv=bkv,
            scale=1.0 / math.sqrt(d), causal=causal, window=window,
            skv=Skv, sq=Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, d).swapaxes(1, 2)
