"""Ahead-of-time compiles of the int8 engine for a described TPU v5e.

Interpret mode runs the Pallas kernels as plain JAX, so it cannot see
what Mosaic refuses on the chip: int32 operands into the MXU, 1-D
blocks whose lane tiling disagrees with the compiler's, VMEM overuse.
These cases lower ``conv2d_int8`` / ``fc_int8`` at every distinct conv
and fc shape of the four paper models, at a serving batch, for one chip
of a described ``v5e:2x2`` topology, and assert the compiled program
holds the Pallas kernel (``tpu_custom_call``). Nothing runs.

The topology is described only inside the module fixture below: only
one process at a time may load the TPU library, so describing it while
a module is imported would break parallel test workers.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import workload as W
from repro.kernels.conv2d_int8.ops import conv2d_int8, fc_int8

SERVE_BATCH = 8


def _layer_cases() -> list[tuple]:
    """One (kind, in_hw, in_ch, out_ch, kernel, stride, groups, pad,
    relu, emit_int32) tuple per distinct engine shape of the paper
    models, with the model.layer that first has it."""
    seen: dict[tuple, str] = {}
    for name, build in W.CNN_MODELS.items():
        m = build()
        last = [l for l in m.layers if l.kind != "pool"][-1]
        hw = m.input_hw
        for lyr in m.layers:
            if lyr.kind != "pool":
                final = lyr is last
                key = (lyr.kind, hw, lyr.in_ch, lyr.out_ch, lyr.kernel,
                       lyr.stride, lyr.groups, lyr.padding(hw),
                       not final, final)
                seen.setdefault(key, f"{name}.{lyr.name}")
            hw = lyr.out_hw(hw)
    return [(label, key) for key, label in seen.items()]


CASES = _layer_cases()


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described device's compile lands in the persistent cache but
    # cannot be read back without a chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("label,case", CASES, ids=[c[0] for c in CASES])
def test_int8_engine_compiles_for_v5e(one_chip, label, case):
    kind, hw, cin, cout, k, stride, groups, pad, relu, emit = case
    shift = _sds((cout,), jnp.int32, one_chip)
    bias = _sds((cout,), jnp.int32, one_chip)
    if kind == "fc":
        x = _sds((SERVE_BATCH, cin), jnp.int8, one_chip)
        w = _sds((cin, cout), jnp.int8, one_chip)
        lowered = fc_int8.lower(x, w, shift, bias, relu=relu,
                                interpret=False, emit_int32=emit)
    else:
        x = _sds((SERVE_BATCH, hw, hw, cin), jnp.int8, one_chip)
        w = _sds((k, k, cin // groups, cout), jnp.int8, one_chip)
        lowered = conv2d_int8.lower(x, w, shift, bias, stride=stride,
                                    padding=(pad, pad), groups=groups,
                                    relu=relu, interpret=False,
                                    emit_int32=emit)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text(), label


@pytest.mark.parametrize("hw,order", [(227, (0, 2, 1)), (227, (0, 1, 2)),
                                      (224, (0, 1, 2))],
                         ids=["227-hcw", "227-hwc", "224-hwc"])
def test_quantize_in_compiles_for_v5e(one_chip, hw, order):
    """The first stage's quantize-in at the served batch and the benchmark
    cells' frame sizes, for frames laid out (H, C, W) as a pool fetched
    from the chip is, and C-ordered as users send them: a flat float32
    batch in, the int8 NHWC batch out."""
    from repro.core.program import compile_model
    from repro.models import cnn
    m = W.CNNModel("qin", 8, 3, (W.ConvLayer("fc", 8 * 8 * 3, 10, 1,
                                             kind="fc"),))
    prog = compile_model(m, cnn.init_params(m, jax.random.PRNGKey(0)),
                         bits=8, calib_batch=jnp.ones((1, 8, 8, 3)))
    runner = prog.compile_stage_runner(0, 1)
    shape = tuple((hw, hw, 3)[a] for a in order)
    flat = _sds((SERVE_BATCH, hw * hw * 3), jnp.float32, one_chip)
    compiled = runner.quantize_in.lower(flat, order, shape).compile()
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (SERVE_BATCH, hw, hw, 3) and out.dtype == jnp.int8
