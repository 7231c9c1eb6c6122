"""Elastic rescale demo: train on 8 CPU devices, checkpoint, lose half the
"pod", re-plan with Algorithm 1 for the surviving devices, restore the
checkpoint re-sharded onto the smaller mesh, and keep training — the
paper's "regenerate the accelerator for the new resource budget" at mesh
scale.

  python examples/elastic_rescale.py      (sets its own XLA device count)
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile

import jax

from repro import checkpointing as ckpt
from repro import optim
from repro.configs import ARCHS
from repro.configs.base import reduced
from repro.data.pipeline import DataConfig, TokenStream
from repro.launch import steps as STEPS
from repro.models import transformer as T
from repro.runtime import sharding as SH
from repro.runtime.fault_tolerance import elastic_replan


AUTO2 = (jax.sharding.AxisType.Auto,) * 2


def mk_mesh(n_data, n_model):
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=AUTO2)


def place(tree, shardings):
    return jax.tree.map(jax.device_put, tree, shardings)


def main():
    cfg = reduced(ARCHS["yi-6b"]).scaled(vocab=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    dc = DataConfig(global_batch=8, seq_len=16, vocab=cfg.vocab)
    stream = TokenStream(dc)
    step = jax.jit(STEPS.make_train_step(cfg, lr=1e-3, remat=False))

    with tempfile.TemporaryDirectory() as ckdir:
        # --- phase 1: 8 devices (4 data x 2 model)
        mesh = mk_mesh(4, 2)
        psh = SH.param_shardings(cfg, mesh, params, fsdp=False)
        params8 = place(params, psh)
        opt = optim.adamw_init(params8)
        with jax.set_mesh(mesh):
            for i in range(6):
                params8, opt, m = step(params8, opt, next(stream))
        print(f"[8 devices] step 6 loss {float(m['loss']):.4f}")
        ckpt.save(ckdir, 6, params8)

        # --- failure: pod shrinks to 4 devices; re-plan + re-shard
        plan = elastic_replan(ARCHS["yi-6b"], 4, seq_len=4096,
                              global_batch=256)
        print(f"[re-plan] surviving 4 chips -> stages x tp = "
              f"{plan.n_stages} x {plan.tensor_parallel}, "
              f"util {plan.utilization:.2f}")
        mesh4 = jax.make_mesh((2, 2), ("data", "model"), axis_types=AUTO2,
                              devices=jax.devices()[:4])
        psh4 = SH.param_shardings(cfg, mesh4, params, fsdp=False)
        params4 = ckpt.restore_resharded(ckdir, 6, params, psh4)
        opt4 = optim.adamw_init(params4)
        stream.seek(6)
        with jax.set_mesh(mesh4):
            for i in range(6):
                params4, opt4, m = step(params4, opt4, next(stream))
        print(f"[4 devices] step 12 loss {float(m['loss']):.4f} "
              f"(resumed from the re-sharded checkpoint)")


if __name__ == "__main__":
    main()
