"""Pure-jnp oracle for the int8 conv/GEMM engine (paper Fig. 3).

The hardware pipeline: int8 activations x int8 weights -> int32 partial
sums -> (+bias, ReLU) -> per-output-channel shift + truncate to int8. The
conv is expressed as an implicit GEMM over int8 im2col patches (the
activation line buffer's address generation), which is exactly what the
Pallas kernel computes in MXU tiles. Patch features are ordered
``(r, s, c)`` so ``w[R,S,C,M].reshape(R*S*C, M)`` matches directly.
"""

from __future__ import annotations

import jax.numpy as jnp

Pad2 = tuple[tuple[int, int], tuple[int, int]]


def requantize_ref(acc: jnp.ndarray, shift: jnp.ndarray,
                   bias: jnp.ndarray | None = None,
                   relu: bool = False) -> jnp.ndarray:
    """The fused epilogue on raw int32 accumulators: bias add, optional
    ReLU, then the shared saturating signed shift + clip to int8
    (``quant.requantize_output`` — the Pallas kernel epilogue inlines the
    identical math, pinned by the bit-identity tests)."""
    from repro.core import quant
    if bias is not None:
        acc = acc + bias.astype(jnp.int32)[None, :]
    if relu:
        acc = jnp.maximum(acc, 0)
    return quant.requantize_output(acc, 0, shift[None, :].astype(jnp.int32),
                                   bits=8)


def gemm_int8_ref(x: jnp.ndarray, w: jnp.ndarray, shift: jnp.ndarray,
                  bias: jnp.ndarray | None = None,
                  relu: bool = False) -> jnp.ndarray:
    """x [N, K] int8, w [K, M] int8, shift [M] int32 (signed shift bits).
    Returns int8 [N, M]: clip((relu?)(x @ w + bias) >> shift)."""
    acc = jnp.matmul(x.astype(jnp.int32), w.astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    return requantize_ref(acc, shift, bias, relu)


def same_padding(in_hw: int, kernel: int, stride: int) -> tuple[int, int]:
    """TF/XLA "SAME" pad pair for one spatial dim."""
    out = -(-in_hw // stride)
    total = max((out - 1) * stride + kernel - in_hw, 0)
    return total // 2, total - total // 2


def im2col_int8(x: jnp.ndarray, R: int, S: int, stride: int,
                pad: Pad2, k_total: int | None = None) -> jnp.ndarray:
    """int8 im2col with no float materialization: x [B,H,W,C] ->
    [B,Ho,Wo,R*S*C], features ordered (r, s, c). ``pad`` is
    ((top, bottom), (left, right)); zero-padding is exact for the
    symmetric (zero-point-0) po2 formats. ``k_total`` appends zero
    feature columns up to that length."""
    xp = jnp.pad(x, ((0, 0), pad[0], pad[1], (0, 0)))
    Hp, Wp = xp.shape[1], xp.shape[2]
    Ho = (Hp - R) // stride + 1
    Wo = (Wp - S) // stride + 1
    cols = [xp[:, r:r + (Ho - 1) * stride + 1:stride,
               s:s + (Wo - 1) * stride + 1:stride, :]
            for r in range(R) for s in range(S)]
    k = R * S * x.shape[-1]
    if k_total is not None and k_total > k:
        cols.append(jnp.zeros(cols[0].shape[:3] + (k_total - k,), x.dtype))
    return jnp.concatenate(cols, axis=-1)


def _resolve_pad(padding, in_h: int, in_w: int, R: int, S: int,
                 stride: int) -> Pad2:
    if padding == "same":
        return same_padding(in_h, R, stride), same_padding(in_w, S, stride)
    return tuple(tuple(p) for p in padding)  # type: ignore[return-value]


def conv2d_int8_via(gemm_fn, x: jnp.ndarray, w: jnp.ndarray,
                    shift: jnp.ndarray, bias: jnp.ndarray | None = None, *,
                    stride: int = 1, padding="same", groups: int = 1,
                    relu: bool = False, pad_k=None,
                    **gemm_kwargs) -> jnp.ndarray:
    """Conv as implicit GEMM over any engine: one weight-stationary
    ``gemm_fn(patches, w2d, shift, bias, relu=..., **gemm_kwargs)`` per
    channel group. Shared by the jnp oracle and the Pallas route so the
    spatial plumbing (stride, asymmetric padding, groups) cannot drift.

    ``pad_k(K)`` is the reduction length ``gemm_fn`` pads to. The zero
    columns are then built into the patches here: padding the flattened
    patch matrix instead makes the TPU compiler take minutes on the
    large-stride stems (AlexNet/ZF conv1 at batch 8)."""
    R, S, Cg, M = w.shape
    B, H, W, C = x.shape
    assert C == Cg * groups and M % groups == 0, (x.shape, w.shape, groups)
    pad = _resolve_pad(padding, H, W, R, S, stride)
    outs = []
    Mg = M // groups
    K = R * S * Cg
    Kp = K if pad_k is None else pad_k(K)
    for g in range(groups):
        xg = x[..., g * Cg:(g + 1) * Cg]
        patches = im2col_int8(xg, R, S, stride, pad, k_total=Kp)
        _, Ho, Wo, _ = patches.shape
        wg = jnp.pad(w[..., g * Mg:(g + 1) * Mg].reshape(K, Mg),
                     ((0, Kp - K), (0, 0)))
        bg = None if bias is None else bias[g * Mg:(g + 1) * Mg]
        out = gemm_fn(patches.reshape(-1, Kp), wg,
                      shift[g * Mg:(g + 1) * Mg], bg, relu=relu,
                      **gemm_kwargs)
        outs.append(out.reshape(B, Ho, Wo, Mg))
    return outs[0] if groups == 1 else jnp.concatenate(outs, axis=-1)


def conv2d_int8_ref(x: jnp.ndarray, w: jnp.ndarray, shift: jnp.ndarray,
                    bias: jnp.ndarray | None = None, *, stride: int = 1,
                    padding="same", groups: int = 1,
                    relu: bool = False) -> jnp.ndarray:
    """x [B,H,W,C] int8, w [R,S,C/groups,M] int8, shift/bias [M].
    Arbitrary stride, asymmetric padding ((top,bot),(left,right)) or
    "same", and grouped channels. Returns int8 [B,Ho,Wo,M]."""
    return conv2d_int8_via(gemm_int8_ref, x, w, shift, bias, stride=stride,
                           padding=padding, groups=groups, relu=relu)
