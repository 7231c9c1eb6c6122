"""Transformer building blocks: norms, rotary (+M-RoPE), GQA/MLA attention
(with KV cache, sliding window, chunked-softmax long-context path), MLPs and
DeepSeek-style shared+routed MoE.

Everything is a pure function over explicit parameter dicts so the same code
lowers under pjit (NamedSharding inputs) and under shard_map (pipeline
stages), and so `jax.eval_shape` can build abstract parameter trees for the
multi-pod dry-run without allocating 671B parameters on a laptop.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = dict[str, Any]

# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    p = {"w": _dense_init(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def apply_dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    w = p["w"]
    if w.dtype == jnp.int8:
        # Weight-only int8 (the paper's fixed-point insight applied to
        # decode): HBM reads are int8; dequant fuses into the matmul.
        w = w.astype(x.dtype) * p["w_scale"].astype(x.dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"]
    return y


def quantize_params_int8(params: Params) -> Params:
    """Per-output-channel symmetric int8 for every dense weight (and the
    embedding). Halves (vs bf16) the per-token weight traffic that bounds
    decode throughput."""
    def q2d(w):
        w = w.astype(jnp.float32)
        s = jnp.maximum(jnp.abs(w).max(axis=-2, keepdims=True),
                        1e-8) / 127.0
        q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
        return q, jnp.squeeze(s, -2).astype(jnp.float32)

    def visit(node):
        # Dense weights, possibly layer-stacked: [d_in, d_out] or
        # [L, d_in, d_out]. Scales are per-out-channel (and per-layer).
        if isinstance(node, dict) and "w" in node and hasattr(node["w"], "ndim") \
                and node["w"].ndim in (2, 3) and node["w"].dtype != jnp.int8:
            q, s = q2d(node["w"])
            return {**node, "w": q, "w_scale": s}
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                # MoE expert stacks: [E, D, F] (or layer-stacked
                # [L, E, D, F]) arrays — per-(expert, out-channel) scales.
                if k in ("wi", "wg", "wo") and hasattr(v, "ndim") \
                        and getattr(v, "ndim", 0) in (3, 4) \
                        and v.dtype != jnp.int8:
                    q, sc = q2d(v)
                    out[k] = q
                    out[k + "_scale"] = sc
                else:
                    out[k] = visit(v)
            return out
        return node

    # The embedding stays bf16: a decode step gathers only B rows of it,
    # so it never bounds the weight-streaming term.
    return visit(params)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * p["scale"]


def layer_norm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * p["scale"] + p["bias"]


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and M-RoPE)
# ---------------------------------------------------------------------------


def _rope_angles(positions: jnp.ndarray, half: int, theta: float):
    """positions [..., S] -> cos/sin [..., S, half] (float32)."""
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               mrope_sections: tuple[int, ...] | None = None) -> jnp.ndarray:
    """x [B,S,H,hd]; positions [B,S] or [B,S,3] for M-RoPE.

    M-RoPE (Qwen2-VL): the head-dim halves are split into sections, each
    rotated by a different position component (temporal/height/width).
    """
    half = x.shape[-1] // 2
    if mrope_sections is None or positions.ndim == 2:
        cos, sin = _rope_angles(positions, half, theta)       # [B,S,half]
    else:
        secs = list(mrope_sections)
        assert sum(secs) == half, (secs, half)
        coss, sins = [], []
        for j, sec in enumerate(secs):
            freqs = theta ** (-(jnp.arange(sum(secs[:j]), sum(secs[:j]) + sec,
                                           dtype=jnp.float32)) / half)
            ang = positions[..., j].astype(jnp.float32)[..., None] * freqs
            coss.append(jnp.cos(ang))
            sins.append(jnp.sin(ang))
        cos = jnp.concatenate(coss, -1)
        sin = jnp.concatenate(sins, -1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Scaled-dot-product attention cores
# ---------------------------------------------------------------------------


def _sdpa_direct(q, k, v, *, causal: bool, window: int,
                 q_offset: jnp.ndarray | int, kv_len: jnp.ndarray | None,
                 kpos: jnp.ndarray | None = None):
    """q [B,Sq,KV,G,hd], k/v [B,Skv,KV,hd]. fp32 softmax.

    q_offset: absolute position of q[0] (for causal masking w/ cache).
    kv_len: number of valid cache entries (decode), else None.
    kpos: per-slot absolute key positions (ring caches), else arange.
    """
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(Sq)[:, None] + q_offset            # [Sq,1]
    kpos = (jnp.arange(Skv) if kpos is None else kpos)[None, :]
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v)


def _sdpa_chunked(q, k, v, *, causal: bool, window: int, q_offset,
                  chunk: int = 1024):
    """Flash-style online-softmax over KV chunks — O(Sq*chunk) memory.

    Used for the 32k prefill shapes where Sq x Skv logits would not fit.
    """
    B, Sq, KV, G, hd = q.shape
    dv = v.shape[-1]                      # may differ from hd (MLA)
    Skv = k.shape[1]
    n_chunks = max(1, Skv // chunk)
    assert Skv % n_chunks == 0, (Skv, chunk)
    chunk = Skv // n_chunks
    scale = 1.0 / math.sqrt(hd)
    kc = k.reshape(B, n_chunks, chunk, KV, hd)
    vc = v.reshape(B, n_chunks, chunk, KV, dv)
    qpos = jnp.arange(Sq)[:, None] + q_offset

    def step(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        logits = (jnp.einsum("bqkgh,bskh->bkgqs", q, kj)
                  .astype(jnp.float32) * scale)
        kpos = j * chunk + jnp.arange(chunk)[None, :]
        mask = jnp.ones((Sq, chunk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, -1e30)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.swapaxes(1, 3).swapaxes(2, 3).astype(q.dtype)  # -> b q k g h


# Attention implementation switch: "jax" (default; compiles anywhere,
# incl. the 512-device CPU dry-run) or "pallas" (the TPU flash kernel;
# interpret-mode on CPU). Applies to the cache-less full-attention path.
_ATTN_IMPL = "jax"


def set_attention_impl(impl: str) -> None:
    global _ATTN_IMPL
    assert impl in ("jax", "pallas"), impl
    _ATTN_IMPL = impl


def _sdpa_pallas(q, k, v, *, causal, window):
    """Route [B,S,KV,G,hd] GQA tensors through the flash kernel
    (kv heads repeated to full heads)."""
    from repro.kernels.flash_attention.kernel import flash_attention
    B, Sq, KV, G, hd = q.shape
    qf = q.reshape(B, Sq, KV * G, hd)
    kf = jnp.repeat(k, G, axis=2)
    vf = jnp.repeat(v, G, axis=2)
    interpret = jax.devices()[0].platform != "tpu"
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          bq=min(128, Sq), bkv=min(128, Sq),
                          interpret=interpret)
    return out.reshape(B, Sq, KV, G, hd)


def sdpa(q, k, v, *, causal: bool = True, window: int = 0, q_offset=0,
         kv_len=None, kpos=None, chunked_threshold: int = 8192):
    """Dispatch between the direct, chunked, and Pallas attention cores."""
    Sq, Skv = q.shape[1], k.shape[1]
    if (_ATTN_IMPL == "pallas" and kv_len is None and kpos is None
            and Sq == Skv and Sq % min(128, Sq) == 0
            and q.shape[-1] == v.shape[-1]):
        return _sdpa_pallas(q, k, v, causal=causal, window=window)
    if (Sq > 1 and Sq * Skv > chunked_threshold ** 2 and kv_len is None
            and kpos is None):
        return _sdpa_chunked(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    return _sdpa_direct(q, k, v, causal=causal, window=window,
                        q_offset=q_offset, kv_len=kv_len, kpos=kpos)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def gqa_init(key, cfg, dtype) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense(ks[0], d, H * hd, dtype, cfg.qkv_bias),
        "wk": dense(ks[1], d, KV * hd, dtype, cfg.qkv_bias),
        "wv": dense(ks[2], d, KV * hd, dtype, cfg.qkv_bias),
        "wo": dense(ks[3], H * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(hd, dtype)
        p["k_norm"] = rms_norm_init(hd, dtype)
    return p


def gqa_apply(p: Params, cfg, x, positions, *, cache: Params | None = None,
              window: int = 0, cross_kv: tuple | None = None,
              causal: bool = True):
    """Returns (out [B,S,D], new_cache). cache = {"k","v","idx"}.

    cross_kv: (k, v) already projected — encoder-decoder cross attention
    (positions are not rotated in that case, matching the Seamless backbone).
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV
    q = apply_dense(p["wq"], x).reshape(B, S, KV, G, hd)
    if cross_kv is None:
        k = apply_dense(p["wk"], x).reshape(B, S, KV, hd)
        v = apply_dense(p["wv"], x).reshape(B, S, KV, hd)
    else:
        k, v = cross_kv
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q)
        k = rms_norm(p["k_norm"], k)
    causal = causal and cross_kv is None
    if cross_kv is None:
        q = apply_rope(q.reshape(B, S, KV * G, hd), positions, cfg.rope_theta,
                       cfg.mrope_sections if cfg.mrope else None
                       ).reshape(B, S, KV, G, hd)
        k = apply_rope(k, positions, cfg.rope_theta,
                       cfg.mrope_sections if cfg.mrope else None)

    new_cache = None
    kv_len = None
    q_offset = 0
    if cache is not None:
        idx = cache["idx"]
        size = cache["k"].shape[1]
        ring = window > 0 and size <= window
        if ring:
            # Ring buffer: a window-sized cache holds the last `size` keys;
            # RoPE is applied before caching so slot order is irrelevant.
            # Per-slot absolute positions keep causal/window masking exact
            # during multi-token prefill into the ring.
            if S > size:
                k, v = k[:, -size:], v[:, -size:]
            s_eff = min(S, size)
            start = idx + (S - s_eff)
            slots = jnp.mod(start + jnp.arange(s_eff), size)
            knew = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
            vnew = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
            slot_pos = cache.get(
                "slot_pos", jnp.full((size,), -(10 ** 9), jnp.int32))
            slot_pos = slot_pos.at[slots].set(start + jnp.arange(s_eff))
            new_cache = {"k": knew, "v": vnew, "idx": idx + S,
                         "slot_pos": slot_pos}
            out = sdpa(q, knew, vnew, causal=causal, window=window,
                       q_offset=idx, kpos=slot_pos)
            out = out.reshape(B, S, H * hd)
            return apply_dense(p["wo"], out), new_cache
        else:
            k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
            new_cache = {"k": k, "v": v, "idx": idx + S}
            kv_len = idx + S
            q_offset = idx
    out = sdpa(q, k, v, causal=causal, window=window, q_offset=q_offset,
               kv_len=kv_len)
    out = out.reshape(B, S, H * hd)
    return apply_dense(p["wo"], out), new_cache


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek V2/V3)
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype) -> Params:
    d, H = cfg.d_model, cfg.n_heads
    nope, rope, vh = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    ks = jax.random.split(key, 8)
    p: Params = {}
    if qr:
        p["wq_a"] = dense(ks[0], d, qr, dtype)
        p["q_a_norm"] = rms_norm_init(qr, dtype)
        p["wq_b"] = dense(ks[1], qr, H * (nope + rope), dtype)
    else:
        p["wq"] = dense(ks[0], d, H * (nope + rope), dtype)
    p["wkv_a"] = dense(ks[2], d, kvr + rope, dtype)
    p["kv_a_norm"] = rms_norm_init(kvr, dtype)
    p["wkv_b"] = dense(ks[3], kvr, H * (nope + vh), dtype)
    p["wo"] = dense(ks[4], H * vh, d, dtype)
    return p


def mla_apply(p: Params, cfg, x, positions, *, cache: Params | None = None):
    """MLA with low-rank latent KV. Prefill/train: decompressed path.
    Decode: matrix-absorbed path attending directly over the cached latent
    (the memory win that is MLA's point).

    cache = {"ckv" [B,Smax,kvr], "krope" [B,Smax,rope], "idx"}.
    """
    B, S, D = x.shape
    H = cfg.n_heads
    nope, rope, vh = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = apply_dense(p["wq_b"],
                        rms_norm(p["q_a_norm"], apply_dense(p["wq_a"], x)))
    else:
        q = apply_dense(p["wq"], x)
    q = q.reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = apply_dense(p["wkv_a"], x)
    ckv, k_rope = kv_a[..., :kvr], kv_a[..., kvr:]
    ckv = rms_norm(p["kv_a_norm"], ckv)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    k_rope = k_rope[:, :, 0, :]

    wkv_b = p["wkv_b"]["w"]
    if wkv_b.dtype == jnp.int8:
        wkv_b = wkv_b.astype(x.dtype) * p["wkv_b"]["w_scale"].astype(x.dtype)
    wkv_b = wkv_b.reshape(kvr, H, nope + vh)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)

    if cache is not None and S == 1:
        # Absorbed decode: q_nope' = q_nope @ W_uk -> latent space.
        idx = cache["idx"]
        ckv_all = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), idx, axis=1)
        kr_all = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), idx, axis=1)
        new_cache = {"ckv": ckv_all, "krope": kr_all, "idx": idx + S}
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)   # [B,1,H,kvr]
        logits = (jnp.einsum("bshr,btr->bhst", q_lat, ckv_all)
                  + jnp.einsum("bshn,btn->bhst", q_rope, kr_all)
                  ).astype(jnp.float32) * scale
        kpos = jnp.arange(ckv_all.shape[1])[None, None, None, :]
        logits = jnp.where(kpos < idx + S, logits, -1e30)
        probs = jax.nn.softmax(logits, -1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, ckv_all)  # latent out
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)       # [B,1,H,vh]
        out = apply_dense(p["wo"], out.reshape(B, S, H * vh))
        return out, new_cache

    # Decompressed path (train / prefill).
    new_cache = None
    if cache is not None:
        idx = cache["idx"]
        ckv_all = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), idx, axis=1)
        kr_all = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), idx, axis=1)
        new_cache = {"ckv": ckv_all, "krope": kr_all, "idx": idx + S}
    kv = jnp.einsum("btr,rhn->bthn", ckv, wkv_b)             # [B,S,H,n+v]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    # Pack rope part: queries per head, key rope shared across heads.
    q_full = jnp.concatenate([q_nope, q_rope], -1)           # [B,S,H,n+r]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, rope))], -1)
    # Treat H as KV groups of size 1 for the shared sdpa core.
    q5 = q_full[:, :, :, None, :]                            # [B,S,H,1,*]
    out = sdpa(q5, k_full, v, causal=True, q_offset=0)
    out = out[:, :, :, 0, :]
    out = apply_dense(p["wo"], out.reshape(B, S, H * vh))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs and MoE
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, f: int, kind: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"wi": dense(ks[0], d, f, dtype),
                "wg": dense(ks[1], d, f, dtype),
                "wo": dense(ks[2], f, d, dtype)}
    return {"wi": dense(ks[0], d, f, dtype), "wo": dense(ks[1], f, d, dtype)}


def mlp_apply(p: Params, x, kind: str):
    if kind == "swiglu":
        return apply_dense(
            p["wo"], jax.nn.silu(apply_dense(p["wg"], x))
            * apply_dense(p["wi"], x))
    if kind == "geglu":
        return apply_dense(
            p["wo"], jax.nn.gelu(apply_dense(p["wg"], x))
            * apply_dense(p["wi"], x))
    return apply_dense(p["wo"], jax.nn.gelu(apply_dense(p["wi"], x)))


def moe_init(key, cfg, dtype) -> Params:
    d, E, f = cfg.d_model, cfg.moe_n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense(ks[0], d, E, jnp.float32),
        "wi": _dense_init(ks[1], (E, d, f), dtype),
        "wg": _dense_init(ks[2], (E, d, f), dtype),
        "wo": _dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.moe_n_shared:
        p["shared"] = mlp_init(ks[4], d, cfg.moe_d_ff * cfg.moe_n_shared,
                               "swiglu", dtype)
    return p


def moe_apply(p: Params, cfg, x):
    """Top-k MoE dispatcher. Under an active device mesh with a ``model``
    axis (the pjit path), the sort-based dispatch runs inside a local
    shard_map — tokens stay on their data shard, experts are
    expert-parallel over ``model``, and the combine is a psum (a dispatch
    tensor of global-token extent would not fit at 1M tokens x 256
    experts). Without a mesh (single-device smoke tests) the same math runs
    locally."""
    mesh = jax.sharding.get_abstract_mesh()
    if "model" in mesh.axis_names \
            and mesh.axis_sizes and math.prod(mesh.axis_sizes) > 1:
        return _moe_sharded(p, cfg, x, mesh)
    return _moe_local(p, cfg, x)


def _moe_sharded(p: Params, cfg, x, mesh):
    E = cfg.moe_n_experts
    T = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B = x.shape[0]
    n_b = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    bspec = P(batch_axes if B % n_b == 0 and B >= n_b else None, None, None)
    espec = {"router": jax.tree.map(lambda _: P(), p["router"]),
             "wi": P("model", None, None), "wg": P("model", None, None),
             "wo": P("model", None, None)}
    for k in ("wi_scale", "wg_scale", "wo_scale"):
        if k in p:
            espec[k] = P("model", None)
    if "shared" in p:
        espec["shared"] = jax.tree.map(lambda _: P(), p["shared"])

    @partial(jax.shard_map, mesh=mesh, in_specs=(espec, bspec),
             out_specs=(bspec, P()), check_vma=False)
    def run(p_loc, x_loc):
        y, aux = _moe_expert_parallel(p_loc, cfg, x_loc, axis="model",
                                      n_shards=T)
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)
        return y, aux

    return run(p, x)


def _expert_w(p: Params, name: str, dtype):
    w = p[name]
    if w.dtype == jnp.int8:
        return w.astype(dtype) * p[name + "_scale"][:, None, :].astype(dtype)
    return w


def _moe_expert_parallel(p: Params, cfg, x, *, axis: str, n_shards: int):
    """Sort-based dispatch over the local tokens, local experts only,
    psum-combine over the expert-parallel axis."""
    B, S, D = x.shape
    E, k = cfg.moe_n_experts, cfg.moe_top_k
    E_loc = E // n_shards
    Tk = B * S
    C = max(1, int(math.ceil(k * Tk / E * cfg.moe_capacity_factor)))
    xt = x.reshape(Tk, D)
    logits = apply_dense(p["router"], xt.astype(jnp.float32))
    gates = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    off = jax.lax.axis_index(axis) * E_loc
    flat_e = topi.reshape(-1) - off
    flat_w = topv.reshape(-1).astype(xt.dtype)
    in_range = (flat_e >= 0) & (flat_e < E_loc)
    flat_e_c = jnp.where(in_range, flat_e, E_loc)
    order = jnp.argsort(flat_e_c)
    tok_of_slot = order // k
    counts = jax.ops.segment_sum(in_range.astype(jnp.int32), flat_e_c,
                                 num_segments=E_loc + 1)[:E_loc]
    offsets = jnp.cumsum(counts) - counts
    slot = offsets[:, None] + jnp.arange(C)[None, :]
    valid = (jnp.arange(C)[None, :] < counts[:, None]) & (slot < Tk * k)
    slot = jnp.clip(slot, 0, Tk * k - 1)
    tok_idx = tok_of_slot[slot]
    xe = jnp.take(xt, tok_idx.reshape(-1), axis=0).reshape(E_loc, C, D)
    xe = xe * valid[..., None].astype(xt.dtype)
    h = jnp.einsum("ecd,edf->ecf", xe, _expert_w(p, "wi", xe.dtype))
    g = jnp.einsum("ecd,edf->ecf", xe, _expert_w(p, "wg", xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                    _expert_w(p, "wo", xe.dtype))
    w_slot = flat_w[order][slot] * valid.astype(xt.dtype)
    yt = jnp.zeros((Tk, D), xt.dtype).at[tok_idx.reshape(-1)].add(
        (ye * w_slot[..., None]).reshape(E_loc * C, D))
    y = jax.lax.psum(yt.reshape(B, S, D), axis)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, "swiglu")
    density = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1), 0)
    router_prob = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(density * router_prob)
    return y, aux.astype(jnp.float32)


def _moe_local(p: Params, cfg, x):
    """Single-shard fallback of the sort-based dispatch (smoke tests)."""
    B, S, D = x.shape
    E, k = cfg.moe_n_experts, cfg.moe_top_k
    T = B * S
    C = max(1, int(math.ceil(k * T / E * cfg.moe_capacity_factor)))
    xt = x.reshape(T, D)
    logits = apply_dense(p["router"], xt.astype(jnp.float32))
    gates = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(gates, k)                       # [T,k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    flat_e = topi.reshape(-1)                                  # [N], N=T*k
    flat_w = topv.reshape(-1).astype(xt.dtype)
    order = jnp.argsort(flat_e)                                # group by expert
    tok_of_slot = order // k                                   # token per slot
    counts = jax.ops.segment_sum(jnp.ones_like(flat_e), flat_e,
                                 num_segments=E)               # [E]
    offsets = jnp.cumsum(counts) - counts
    slot = offsets[:, None] + jnp.arange(C)[None, :]           # [E,C]
    valid = (jnp.arange(C)[None, :] < counts[:, None]) & (slot < T * k)
    slot = jnp.clip(slot, 0, T * k - 1)
    tok_idx = tok_of_slot[slot]                                # [E,C]
    xe = jnp.take(xt, tok_idx.reshape(-1), axis=0).reshape(E, C, D)
    xe = xe * valid[..., None].astype(xt.dtype)

    h = jnp.einsum("ecd,edf->ecf", xe, _expert_w(p, "wi", xe.dtype))
    g = jnp.einsum("ecd,edf->ecf", xe, _expert_w(p, "wg", xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                    _expert_w(p, "wo", xe.dtype))

    w_slot = flat_w[order][slot] * valid.astype(xt.dtype)      # [E,C]
    yt = jnp.zeros((T, D), xt.dtype).at[tok_idx.reshape(-1)].add(
        (ye * w_slot[..., None]).reshape(E * C, D))
    y = yt.reshape(B, S, D)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, "swiglu")
    # Load-balance auxiliary loss (Switch-style), returned for training.
    density = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1), 0)
    router_prob = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(density * router_prob)
    return y, aux.astype(jnp.float32)
