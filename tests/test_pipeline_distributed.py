"""Multi-device tests (8 host CPU devices in a subprocess): the flexible
pipeline's numerics vs the sequential reference, and the pjit sharding
rules. Run in a subprocess so the main pytest session keeps 1 device."""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import ARCHS, reduced
    from repro.core import pipeline as PL
    from repro.models import transformer as TF
    from repro.models import layers as L

    def run(arch, S, T, K, tol=5e-3, boundaries=None):
        # MoE: no-drop capacity (capacity overflow legitimately differs
        # between microbatched and full-batch dispatch) + wider tolerance
        # (expert psums split across tp reorder bf16 reductions).
        cfg = reduced(ARCHS[arch]).scaled(n_layers=4, vocab=128,
                                          moe_capacity_factor=8.0)
        mesh = PL.make_pipeline_mesh(n_data=8 // (S * T), n_stage=S, n_tp=T)
        params, kind = PL.build_pipeline_params(cfg, S=S,
                                                boundaries=boundaries)
        mask = params.pop("unit_mask")
        units_shape = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            params["units"])
        ctx = PL.PipelineContext(cfg=cfg, unit_kind=kind, S=S, T=T,
                                 n_micro=K)
        loss_fn = PL.pipeline_loss_fn(ctx, mesh, units_shape,
                                      unit_mask=mask)
        B, Sq = 8, 16
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(key, (B, Sq), 0, 128),
                 "labels": jax.random.randint(key, (B, Sq), 0, 128)}
        with jax.set_mesh(mesh):
            loss = float(jax.jit(loss_fn)(params, batch))
            g = jax.jit(jax.grad(loss_fn))(params, batch)
            gn = float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                           for x in jax.tree.leaves(g)))
        # sequential reference
        def ref_loss(params, batch):
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
            Bb, Ss = batch["tokens"].shape
            pos = jnp.broadcast_to(jnp.arange(Ss)[None], (Bb, Ss))
            if cfg.mrope:
                pos = jnp.broadcast_to(pos[..., None], (Bb, Ss, 3))
            S_, Lmax = mask.shape
            for s_ in range(S_):
                for j in range(Lmax):
                    if not bool(mask[s_, j]):
                        continue
                    lp = jax.tree.map(lambda t: t[s_, j], params["units"])
                    x, _, _ = TF._layer_apply(kind, lp, cfg, x, pos, None)
            x = L.rms_norm(params["final_norm"], x)
            logits = (x @ params["lm_head"]["w"]).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, -1)
            nll = -jnp.take_along_axis(
                logp, batch["labels"][..., None], -1)[..., 0]
            return float(nll.mean())
        rl = ref_loss(params, batch)
        assert abs(rl - loss) < tol, (arch, rl, loss)
        assert gn > 0 and np.isfinite(gn), (arch, gn)
        print(f"OK {arch} S={S} T={T} K={K} loss={loss:.4f} ref={rl:.4f}")

    run("yi-6b", 2, 2, 2)       # GQA units, 2-stage x 2-tp
    run("yi-6b", 4, 1, 4)       # 4-stage pure pipeline
    run("qwen2-72b", 2, 2, 2)   # qkv-bias GQA
    run("rwkv6-7b", 2, 2, 2)    # attention-free units
    run("deepseek-v2-236b", 2, 2, 2, tol=2e-2)  # MLA + MoE units
    # Algorithm-1-style nonuniform stage boundaries (3+1 layers)
    run("yi-6b", 2, 2, 2, boundaries=(0, 3, 4))
""")


@pytest.mark.slow
def test_pipeline_matches_reference_multidevice():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=os.path.join(
        os.path.dirname(__file__), ".."), env=env, capture_output=True,
        text=True, timeout=1800)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert res.stdout.count("OK ") == 6, res.stdout + res.stderr


_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.configs import ARCHS, reduced
    from repro.models import transformer as TF
    from repro.runtime import sharding as SH
    from repro.launch import steps as STEPS

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch in ("yi-6b", "deepseek-v2-236b", "rwkv6-7b"):
        cfg = reduced(ARCHS[arch])
        params_sds, opt_sds = STEPS.abstract_state(cfg)
        psh = SH.param_shardings(cfg, mesh, params_sds, fsdp=False)
        # every spec must be constructible for real arrays
        params = TF.init_params(cfg, jax.random.PRNGKey(0))
        placed = jax.tree.map(jax.device_put, params, psh)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "labels": jnp.zeros((8, 16), jnp.int32)}
        bsh = SH.batch_shardings(mesh, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
        with jax.set_mesh(mesh):
            loss, _ = jax.jit(lambda p, b: TF.loss_fn(p, cfg, b))(
                placed, jax.tree.map(jax.device_put, batch, bsh))
        assert bool(jnp.isfinite(loss)), arch
        print("OK", arch, float(loss))
""")


@pytest.mark.slow
def test_pjit_sharding_rules_multidevice():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         env=env, capture_output=True, text=True,
                         timeout=1800)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert res.stdout.count("OK") == 3, res.stdout + res.stderr
