"""Everything a run feeds the system, made from ``--seed``.

One jitted call on the device draws the float weights and biases of
every conv and fc node of the network (``bench/graph.py``, either
form), the calibration frame and the pool of frames that requests cycle
through; the host gets them as numpy arrays, which is the form both the
program's compiler and the reference take. Only the seed and the
configuration's shapes decide the values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import graph

BIAS_STD = 0.05


def _shapes(cfg: dict) -> dict:
    """Weight shape of every conv (HWIO) and fc ((in, out)) node, in the
    network's order."""
    return {n.name: n.weight_shape for n in graph.parse(cfg).compute()}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shapes: tuple, frame: tuple, n_pool: int):
    keys = jax.random.split(key, len(shapes) + 2)
    params = {}
    for k, (name, w) in zip(keys, shapes):
        kw, kb = jax.random.split(k)
        fan_in = int(np.prod(w[:-1]))
        params[name] = {
            "w": jax.random.normal(kw, w, jnp.float32) / np.sqrt(fan_in),
            "b": BIAS_STD * jax.random.normal(kb, (w[-1],), jnp.float32)}
    calib = jax.random.normal(keys[-2], (1,) + frame, jnp.float32)
    pool = jax.random.normal(keys[-1], (n_pool,) + frame, jnp.float32)
    return params, calib, pool


def make_inputs(cfg: dict, seed: int, device=None):
    """-> (params {layer: {"w", "b"}}, calibration batch [1, H, W, C],
    frame pool [P, H, W, C]), all float32 numpy."""
    shapes = tuple(_shapes(cfg).items())
    frame = graph.parse(cfg).frame
    with jax.default_device(device or jax.devices()[0]):
        key = jax.random.PRNGKey(seed)
        out = _draw(key, shapes, frame, int(cfg["pool_frames"]))
        params, calib, pool = jax.device_get(out)
    return params, np.asarray(calib), np.asarray(pool)
