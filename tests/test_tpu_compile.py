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
