import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, with NO parameter allocation
(ShapeDtypeStruct stand-ins), and extract the roofline inputs:

  * compiled.memory_analysis()  -> bytes per device (fits-in-HBM proof)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes accessed
  * lowered HLO text            -> per-collective operand bytes

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k \
      --dist pipeline --stages 4    # the paper's pipeline path
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get as get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, cache_specs, cell_is_runnable,
                                 input_specs)
from repro.launch import steps as STEPS
from repro.runtime import sharding as SH

from repro.launch.hlo_stats import collective_bytes  # noqa: E402


def _mesh_for(name: str):
    return make_production_mesh(multi_pod=(name == "multipod"))


def run_cell(arch: str, shape: str, mesh_name: str, dist: str = "pjit",
             stages: int = 0, quant: str = "none") -> dict:
    cfg = get_arch(arch)
    case = SHAPES[shape]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "skipped", "reason": why}
    mesh = _mesh_for(mesh_name)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if dist == "pipeline":
            res = _run_pipeline_cell(cfg, case, mesh, mesh_name, stages)
        else:
            res = _run_pjit_cell(cfg, case, mesh, mesh_name,
                                 dp_model=(dist == "dp"), quant=quant)
    res.update(arch=arch, shape=shape, mesh=mesh_name, dist=dist,
               quant=quant, compile_s=round(time.time() - t0, 1),
               status="ok")
    return res


def _analyze(lowered, compiled, n_dev: int) -> dict:
    out: dict = {}
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            k: int(getattr(ma, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        out["memory_error"] = str(e)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        out["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and
                       k in ("flops", "bytes accessed",
                             "bytes accessed output", "optimal_seconds")}
    except Exception as e:  # pragma: no cover
        out["cost_error"] = str(e)
    try:
        txt = compiled.as_text()
    except Exception:
        txt = lowered.as_text()
    out["collectives"] = collective_bytes(txt)
    out["n_devices"] = n_dev
    return out


def _run_pjit_cell(cfg, case, mesh, mesh_name, dp_model: bool = False,
                   quant: str = "none") -> dict:
    n_dev = mesh.devices.size
    batch_sds = input_specs(cfg, case)
    if dp_model:
        # Repurpose the model axis as extra data parallelism (small-model
        # optimization, EXPERIMENTS.md §Perf): params replicated over it,
        # batch sharded over (pod, data, model).
        batch_sh = _dp_batch_shardings(mesh, batch_sds)
    else:
        batch_sh = SH.batch_shardings(mesh, batch_sds,
                                      seq_shard=(case.mode == "prefill"))

    if case.mode == "train":
        params_sds, opt_sds = STEPS.abstract_state(cfg)
        param_sh = SH.param_shardings(cfg, mesh, params_sds,
                                      fsdp=None if not dp_model else False)
        if dp_model:
            param_sh = jax.tree.map(_strip_model_axis, param_sh)
        opt_sh = _opt_shardings(opt_sds, param_sh, mesh)
        step = STEPS.make_train_step(cfg)
        lowered = jax.jit(
            step, in_shardings=(param_sh, opt_sh, batch_sh),
            donate_argnums=(0, 1),
        ).lower(params_sds, opt_sds, batch_sds)
    else:
        from repro.models import layers as LYR
        from repro.models import transformer as TF
        params_sds = jax.eval_shape(lambda: TF.init_params(cfg))
        if quant == "int8":
            params_sds = jax.eval_shape(LYR.quantize_params_int8,
                                        params_sds)
        param_sh = SH.param_shardings(cfg, mesh, params_sds)
        cache_sds = cache_specs(cfg, case)
        cache_sh = SH.cache_shardings(mesh, cache_sds)
        if case.mode == "prefill":
            step = STEPS.make_prefill_step(cfg)
        else:
            step = STEPS.make_serve_step(cfg)
        lowered = jax.jit(
            step, in_shardings=(param_sh, cache_sh, batch_sh),
            donate_argnums=(1,),
        ).lower(params_sds, cache_sds, batch_sds)
    compiled = lowered.compile()
    res = _analyze(lowered, compiled, n_dev)
    print(compiled.memory_analysis())
    return res


def _strip_model_axis(sh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = tuple(None if ax == "model" else ax for ax in sh.spec)
    return NamedSharding(sh.mesh, P(*spec))


def _dp_batch_shardings(mesh, batch_shape):
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def one(leaf):
        if leaf.ndim and leaf.shape[0] % n == 0 and leaf.shape[0] >= n:
            return NamedSharding(mesh, P(axes))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(one, batch_shape)


def _opt_shardings(opt_sds, param_sh, mesh):
    """Moments inherit param shardings; ZeRO-1 additionally splits the
    first still-replicated dim over 'data' when divisible. q8-encoded
    moments shard their block dim over the whole mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    n_all = 1
    for a in axes:
        n_all *= mesh.shape[a]

    def is_q8(n):
        return isinstance(n, dict) and set(n) == {"q", "scale", "shape"}

    def inherit(sds, psh):
        if is_q8(sds):
            blocks = sds["q"].shape[0]
            spec = P(axes) if blocks % n_all == 0 else P()
            return {"q": NamedSharding(mesh, spec),
                    "scale": NamedSharding(mesh, spec),
                    "shape": NamedSharding(mesh, P())}
        spec = list(psh.spec) + [None] * (sds.ndim - len(psh.spec))
        if "data" in mesh.shape and "data" not in spec:
            nd = mesh.shape["data"]
            for i, s in enumerate(spec):
                if s is None and sds.shape[i] % nd == 0 and sds.shape[i] >= nd:
                    spec[i] = "data"
                    break
        return NamedSharding(mesh, P(*spec))

    from repro.optim import AdamWState
    mu = jax.tree.map(inherit, opt_sds.mu, param_sh, is_leaf=is_q8)
    nu = jax.tree.map(inherit, opt_sds.nu, param_sh, is_leaf=is_q8)
    err = (jax.tree.map(lambda _: NamedSharding(mesh, P()), opt_sds.err)
           if opt_sds.err is not None else None)
    return AdamWState(NamedSharding(mesh, P()), mu, nu, err)


def _run_pipeline_cell(cfg, case, mesh, mesh_name, stages: int) -> dict:
    """The paper's flexible-pipeline path: model axis -> stage x tp."""
    from repro.core import pipeline as PL
    from repro.core.allocator import plan_pipeline
    from repro.core.workload import lm_layer_workloads

    if case.mode not in ("train", "prefill"):
        raise ValueError("pipeline dry-run covers train/prefill shapes")
    if not PL.supports_pipeline(cfg):
        return {"status": "unsupported", "reason": "unit kind"}
    train = case.mode == "train"
    layers = lm_layer_workloads(cfg, seq_len=case.seq_len,
                                batch=case.global_batch, mode=case.mode)
    n_pod = mesh.shape.get("pod", 1)
    plan = plan_pipeline(
        layers, model_axis=16, data_axis=16 * n_pod,
        global_batch=case.global_batch, seq_len=case.seq_len, train=train,
        d_model=cfg.d_model, allow_infeasible=not train,
        stage_choices=[stages] if stages else None)
    S, T = plan.n_stages, plan.tensor_parallel
    pmesh = PL.make_pipeline_mesh(16, S, T, n_pod=n_pod)
    params, kind = PL.build_pipeline_params(cfg, S, abstract=True)
    mask_shape = params.pop("unit_mask")
    import numpy as np
    mask = jnp.asarray(np.ones(mask_shape.shape, bool))
    units_shape = params["units"]
    K = min(plan.microbatches,
            case.global_batch // (16 * n_pod))
    K = max(K, 1)
    ctx = PL.PipelineContext(cfg=cfg, unit_kind=kind, S=S, T=T, n_micro=K)
    with jax.set_mesh(pmesh):
        batch_sds = input_specs(cfg, case)
        if train:
            loss_fn = PL.pipeline_loss_fn(ctx, pmesh, units_shape,
                                          unit_mask=mask)
            lowered = jax.jit(jax.grad(loss_fn)).lower(params, batch_sds)
        else:
            fn = PL.pipeline_prefill_fn(ctx, pmesh, units_shape,
                                        unit_mask=mask)
            lowered = jax.jit(fn).lower(params, batch_sds)
        compiled = lowered.compile()
        res = _analyze(lowered, compiled, pmesh.devices.size)
        print(compiled.memory_analysis())
    res["plan"] = {"S": S, "T": T, "microbatches": K,
                   "boundaries": list(plan.boundaries)[:8],
                   "predicted_util": plan.utilization}
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod",
                                                      "both"))
    ap.add_argument("--dist", default="pjit",
                    choices=("pjit", "pipeline", "dp"))
    ap.add_argument("--quant", default="none", choices=("none", "int8"))
    ap.add_argument("--stages", type=int, default=0)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = list(ARCHS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}_{shape}_{mesh_name}_{args.dist}"
                if args.quant != "none":
                    tag += f"_{args.quant}"
                try:
                    res = run_cell(arch, shape, mesh_name, args.dist,
                                   args.stages, args.quant)
                except Exception as e:
                    res = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "dist": args.dist, "status": "error",
                           "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)
                print(f"[{res['status']:9s}] {tag} "
                      f"({res.get('compile_s', '-')}s)", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
