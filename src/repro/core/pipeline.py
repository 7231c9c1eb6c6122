"""Flexible layer-wise pipeline executor (the paper's architecture on a TPU
mesh).

The pod's ``model`` axis is factored into ``stage x tp`` (chosen by the
mesh-mode allocator, core/allocator.plan_pipeline — the Algorithm-1
analogue). All stages are resident simultaneously; microbatches stream
through via ``lax.ppermute`` on the stage axis (the activation line-buffer
analogue), with a GPipe fill/drain schedule driven by ``lax.scan`` so the
whole computation is reverse-differentiable. Within a stage, layers run
Megatron-style tensor parallel over the ``tp`` axis with manual psums.

Embedding and LM head run *outside* the shard_map body (sharded over the
full stage*tp product via NamedSharding) so their large vocab GEMMs are
computed once at full parallelism instead of once per stage per tick — the
analogue of the paper keeping the FC engines out of the row pipeline.

Correspondence to the FPGA original (DESIGN.md §2): engines = device
groups, cycles = seconds, K-row groups = microbatches; the flexible
activation buffer's producer/consumer re-layout becomes the inter-stage
collective, which is what frees the allocator to give different stages
different parallelisms (DNNBuilder's constraint, lifted).
"""

from __future__ import annotations

import dataclasses
import math
import re
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import recurrent as R

Params = dict[str, Any]

SUPPORTED_UNIT_KINDS = ("attn", "attn_local", "moe", "mla", "mla_moe",
                        "rwkv")


def make_pipeline_mesh(n_data: int, n_stage: int, n_tp: int,
                       n_pod: int = 1) -> Mesh:
    """Factor the pod's model axis into (stage, tp); same devices as the
    production (data, model) mesh, viewed as the pipeline grid."""
    shape = (n_pod, n_data, n_stage, n_tp) if n_pod > 1 else \
        (n_data, n_stage, n_tp)
    axes = (("pod", "data", "stage", "tp") if n_pod > 1 else
            ("data", "stage", "tp"))
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# ---------------------------------------------------------------------------
# Stage-stacked parameters
# ---------------------------------------------------------------------------


def stage_stack(unit_params: Params, boundaries: tuple[int, ...]):
    """[n_units, ...] leaves -> ([S, Lmax, ...] padded, mask [S, Lmax]).

    `boundaries` may be non-uniform — that is Algorithm 1's output when the
    units (or the stage prologue/epilogue work) are heterogeneous."""
    S = len(boundaries) - 1
    counts = [boundaries[i + 1] - boundaries[i] for i in range(S)]
    lmax = max(counts)
    idx = np.zeros((S, lmax), np.int32)
    mask = np.zeros((S, lmax), np.bool_)
    for s in range(S):
        for j in range(lmax):
            idx[s, j] = boundaries[s] + min(j, max(counts[s] - 1, 0))
            mask[s, j] = j < counts[s]
    stacked = jax.tree.map(lambda t: t[idx], unit_params)
    return stacked, jnp.asarray(mask)


def uniform_boundaries(n_units: int, S: int) -> tuple[int, ...]:
    base, rem = divmod(n_units, S)
    bounds = [0]
    for s in range(S):
        bounds.append(bounds[-1] + base + (1 if s < rem else 0))
    return tuple(bounds)


# ---------------------------------------------------------------------------
# Manual-TP unit application (runs inside shard_map)
# ---------------------------------------------------------------------------


def _tp_view(cfg: ModelConfig, T: int) -> ModelConfig:
    """Per-tp-rank view: heads / ff / experts divided by T (kv heads
    replicated when T > n_kv_heads)."""
    return cfg.scaled(
        n_heads=cfg.n_heads // T,
        n_kv_heads=(cfg.n_kv_heads // T if cfg.n_kv_heads % T == 0
                    else cfg.n_kv_heads),
        d_ff=cfg.d_ff // T,
        moe_n_experts=(cfg.moe_n_experts // T if cfg.moe_n_experts else 0),
    )


def _apply_unit_tp(kind: str, cfg: ModelConfig, lp: Params, x, positions,
                   T: int):
    """One transformer unit, tensor-parallel over mesh axis 'tp'. Parameter
    leaves arrive pre-sliced; block outputs are psummed so the residual
    stream stays replicated within the stage."""
    lcfg = _tp_view(cfg, T)
    h_in = L.rms_norm(lp["ln1"], x)
    if kind == "rwkv":
        h, _ = R.rwkv6_block_apply(lp["rwkv"], lcfg, h_in, state=None)
        x = x + jax.lax.psum(h, "tp")
        h2, _ = R.rwkv6_channel_mix(lp["rwkv"], L.rms_norm(lp["ln2"], x),
                                    jnp.zeros_like(x[:, 0]))
        return x + jax.lax.psum(h2, "tp")
    if kind in ("mla", "mla_moe"):
        h, _ = L.mla_apply(lp["attn"], lcfg, h_in, positions)
    else:
        h, _ = L.gqa_apply(lp["attn"], lcfg, h_in, positions,
                           window=cfg.window if kind == "attn_local" else 0)
    x = x + jax.lax.psum(h, "tp")
    h_in2 = L.rms_norm(lp["ln2"], x)
    if kind.endswith("moe"):
        h2 = _moe_apply_tp(lp["mlp"], cfg, h_in2, T)
    else:
        h2 = L.mlp_apply(lp["mlp"], h_in2, cfg.mlp_kind)
    return x + jax.lax.psum(h2, "tp")


def _moe_apply_tp(p: Params, cfg, x, T):
    """Expert-parallel MoE: identical routing on every tp rank (router
    replicated); each rank runs its E/T local experts; the caller's psum
    combines (EP without an explicit all-to-all — the dispatch stays local
    because activations are tp-replicated)."""
    B, S, D = x.shape
    E, k = cfg.moe_n_experts, cfg.moe_top_k
    E_loc = E // T
    Tk = B * S
    C = max(1, int(math.ceil(k * Tk / E * cfg.moe_capacity_factor)))
    xt = x.reshape(Tk, D)
    logits = L.apply_dense(p["router"], xt.astype(jnp.float32))
    gates = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    off = jax.lax.axis_index("tp") * E_loc
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
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"])
    w_slot = flat_w[order][slot] * valid.astype(xt.dtype)
    yt = jnp.zeros((Tk, D), xt.dtype).at[tok_idx.reshape(-1)].add(
        (ye * w_slot[..., None]).reshape(E_loc * C, D))
    y = yt.reshape(B, S, D)
    if "shared" in p:
        y = y + L.mlp_apply(p["shared"], x, "swiglu")
    return y


def _tp_dim_for(path: str, ndim: int, cfg: ModelConfig, T: int,
                shape: tuple) -> int | None:
    """Which dim of a stacked [S, Lmax, ...] unit leaf is tp-sharded.

    Patterns are anchored at a path-segment boundary so e.g. `cm_wv/w`
    (row-sharded) never matches the generic `wv/w` column rule."""
    col = [r"(^|/)(wq|wk|wv)/w$", r"mlp/(wi|wg)/w$", r"(wq_b|wkv_b)/w$",
           r"shared/(wi|wg)/w$", r"rwkv/(wr|wk|wv|wg)/w$",
           r"rwkv/cm_wk/w$"]
    row = [r"(^|/)wo/w$", r"shared/wo/w$", r"rwkv/cm_wv/w$"]
    if re.search(r"mlp/(wi|wg|wo)$", path):        # MoE stacks [S,L,E,D,F]
        return 2
    if re.search(r"rwkv/(w0|ln_x_scale|ln_x_bias|dec_w2)$", path) \
            or re.search(r"(^|/)(wq|wk|wv|wi|wg)/b$", path):
        return ndim - 1
    if re.search(r"rwkv/u$", path):
        return ndim - 2
    for pat in row:
        if re.search(pat, path):
            return ndim - 2
    for pat in col:
        if re.search(pat, path):
            if re.search(r"(^|/)(wk|wv)/w$", path) \
                    and cfg.n_kv_heads % T != 0:
                return None                         # replicate small kv
            return ndim - 1
    return None


def _unit_specs(cfg: ModelConfig, T: int, units_shape) -> Any:
    def one(path, leaf):
        pstr = "/".join(str(getattr(k, "key", k)) for k in path)
        d = _tp_dim_for(pstr, leaf.ndim, cfg, T, leaf.shape)
        dims: list = ["stage"] + [None] * (leaf.ndim - 1)
        if d is not None and leaf.shape[d] % T == 0:
            dims[d] = "tp"
        return P(*dims)
    return jax.tree_util.tree_map_with_path(one, units_shape)


# ---------------------------------------------------------------------------
# The pipelined body + outer loss
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelineContext:
    cfg: ModelConfig
    unit_kind: str
    S: int                  # stages
    T: int                  # tensor parallel within stage
    n_micro: int
    remat: bool = True


def pipeline_body_fn(ctx: PipelineContext, mesh: Mesh, units_shape):
    """shard_mapped GPipe body: x0 [B,Seq,D] -> ys [S, B, Seq, D] (take
    [-1] outside). Stage s applies its unit slice; microbatches advance via
    ppermute each tick."""
    cfg, S, T, K = ctx.cfg, ctx.S, ctx.T, ctx.n_micro
    batch_axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    pos_ndim = 3 if cfg.mrope else 2
    unit_specs = _unit_specs(cfg, T, units_shape)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(unit_specs, P("stage", None),
                       P(batch_axes, None, None), P(batch_axes, None)
                       if pos_ndim == 2 else P(batch_axes, None, None)),
             out_specs=P("stage", batch_axes, None, None),
             check_vma=False)
    def body(units, unit_mask, x0, positions):
        Bl, Seq, D = x0.shape
        mbB = Bl // K
        x_mb = x0.reshape(K, mbB, Seq, D)
        pos_mb = positions.reshape((K, mbB, Seq) + ((3,) if pos_ndim == 3
                                                    else ()))
        stage = jax.lax.axis_index("stage")
        my_units = jax.tree.map(lambda t: t[0], units)
        my_mask = unit_mask[0]
        perm = [(i, i + 1) for i in range(S - 1)]

        def apply_stage(x, pos):
            def unit_body(x, uj):
                up, msk = uj
                y = _apply_unit_tp(ctx.unit_kind, cfg, up, x, pos, T)
                return jnp.where(msk, y, x), None
            fn = jax.checkpoint(unit_body) if ctx.remat else unit_body
            x, _ = jax.lax.scan(fn, x, (my_units, my_mask))
            return x

        def tick(carry, t):
            buf, out = carry
            m = jnp.clip(t - stage, 0, K - 1)
            xm = jax.lax.dynamic_index_in_dim(x_mb, m, 0, False)
            pm = jax.lax.dynamic_index_in_dim(pos_mb, m, 0, False)
            x_in = jnp.where(stage == 0, xm, buf)
            y = apply_stage(x_in, pm)
            take = ((t - stage >= 0) & (t - stage < K) & (stage == S - 1))
            upd = jax.lax.dynamic_update_slice_in_dim(
                out, y[None].astype(out.dtype), m, 0)
            out = jnp.where(take, upd, out)
            buf = jax.lax.ppermute(y, "stage", perm) if S > 1 else y
            return (buf, out), None

        buf0 = jnp.zeros((mbB, Seq, D), x0.dtype)
        out0 = jnp.zeros((K, mbB, Seq, D), x0.dtype)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0),
                                   jnp.arange(K + S - 1))
        return out.reshape(Bl, Seq, D)[None]      # [1(stage), Bl, Seq, D]

    return body


def pipeline_loss_fn(ctx: PipelineContext, mesh: Mesh, units_shape,
                     unit_mask=None):
    """Full pipelined training loss: embed -> pipeline body -> head + CE.

    Embed/head are sharded over ("stage","tp") jointly (= the pod's model
    axis) via sharding constraints, mirroring the paper's choice to keep FC
    engines outside the row pipeline."""
    cfg = ctx.cfg
    body = pipeline_body_fn(ctx, mesh, units_shape)
    batch_axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    vp = ("stage", "tp")

    def loss(params, batch):
        if "tokens" in batch:
            tokens = batch["tokens"]
            B, Seq = tokens.shape
            emb = jax.lax.with_sharding_constraint(
                params["embed"], NamedSharding(mesh, P(vp, None)))
            x0 = jnp.take(emb, tokens, axis=0)
        else:
            x0 = batch["embeds"]
            B, Seq = x0.shape[:2]
        x0 = jax.lax.with_sharding_constraint(
            x0, NamedSharding(mesh, P(batch_axes, None, None)))
        if "positions" in batch:
            positions = batch["positions"]
        else:
            positions = jnp.broadcast_to(jnp.arange(Seq)[None], (B, Seq))
            if cfg.mrope:
                positions = jnp.broadcast_to(positions[..., None],
                                             (B, Seq, 3))
        mask = unit_mask if unit_mask is not None else params["unit_mask"]
        ys = body(params["units"], mask, x0, positions)
        y = ys[-1]
        y = L.rms_norm(params["final_norm"], y)
        if cfg.tie_embeddings:
            head = jax.lax.with_sharding_constraint(
                params["embed"].T, NamedSharding(mesh, P(None, vp)))
        else:
            head = jax.lax.with_sharding_constraint(
                params["lm_head"]["w"], NamedSharding(mesh, P(None, vp)))
        logits = (y @ head).astype(jnp.float32)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    return loss


def pipeline_prefill_fn(ctx: PipelineContext, mesh: Mesh, units_shape,
                        unit_mask=None):
    """Forward-only pipelined prefill: embed -> body -> last-token logits.

    (Serving would additionally emit the per-stage KV caches; the collective
    and compute structure measured here is identical — the cache write is a
    local store.)"""
    cfg = ctx.cfg
    body = pipeline_body_fn(ctx, mesh, units_shape)
    batch_axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    vp = ("stage", "tp")

    def prefill(params, batch):
        tokens = batch["tokens"]
        B, Seq = tokens.shape
        emb = jax.lax.with_sharding_constraint(
            params["embed"], NamedSharding(mesh, P(vp, None)))
        x0 = jax.lax.with_sharding_constraint(
            jnp.take(emb, tokens, axis=0),
            NamedSharding(mesh, P(batch_axes, None, None)))
        positions = jnp.broadcast_to(jnp.arange(Seq)[None], (B, Seq))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None], (B, Seq, 3))
        mask = unit_mask if unit_mask is not None else params["unit_mask"]
        ys = body(params["units"], mask, x0, positions)
        y = L.rms_norm(params["final_norm"], ys[-1][:, -1:])
        if cfg.tie_embeddings:
            head = params["embed"].T
        else:
            head = params["lm_head"]["w"]
        head = jax.lax.with_sharding_constraint(
            head, NamedSharding(mesh, P(None, vp)))
        return (y @ head).astype(jnp.float32)[:, 0]

    return prefill


# ---------------------------------------------------------------------------
# Building pipeline params from a config
# ---------------------------------------------------------------------------


def dominant_segment(cfg: ModelConfig):
    from repro.models import transformer as TF
    segs = TF.segments(cfg)
    return max(segs, key=lambda s: s.count)


def supports_pipeline(cfg: ModelConfig) -> bool:
    return dominant_segment(cfg).kind in SUPPORTED_UNIT_KINDS


def build_pipeline_params(cfg: ModelConfig, S: int,
                          boundaries: tuple[int, ...] | None = None,
                          abstract: bool = False) -> tuple[Params, str]:
    """Returns (params, unit_kind). The dominant homogeneous segment forms
    the pipeline units; remaining small segments are folded into the nearest
    stage... (v1: the dominant segment covers the pipeline; for every
    assigned arch it is >= 93% of FLOPs — leading dense layers of the MoE
    archs ride along in stage 0's unit list only if same-kind)."""
    from repro.models import transformer as TF

    main = dominant_segment(cfg)
    if main.kind not in SUPPORTED_UNIT_KINDS:
        raise ValueError(f"pipeline unsupported for unit kind {main.kind}")
    bounds = boundaries or uniform_boundaries(main.count, S)
    dtype = jnp.dtype(cfg.dtype)

    def make():
        key = jax.random.PRNGKey(0)
        units = [TF._layer_init(main.kind, cfg, jax.random.fold_in(key, i),
                                dtype) for i in range(main.count)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *units)
        staged, mask = stage_stack(stacked, bounds)
        return {
            "embed": (jax.random.normal(key, (cfg.vocab, cfg.d_model),
                                        jnp.float32) * 0.02).astype(dtype),
            "units": staged,
            "unit_mask": mask,
            "final_norm": L.rms_norm_init(cfg.d_model, dtype),
            **({} if cfg.tie_embeddings else
               {"lm_head": L.dense(jax.random.fold_in(key, 99),
                                   cfg.d_model, cfg.vocab, dtype)}),
        }

    params = jax.eval_shape(make) if abstract else make()
    return params, main.kind
