"""Profiler spans on the served path and stable device names: a traced
run through ``Server`` shows every ``serve.*`` span, each on its thread
and inside its parent, the executor spans of one batch share its
number, stage jits and the Pallas kernel carry fixed names, and
``Server.stats()`` counts each stage's executables."""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import workload as W
from repro.core.program import compile_model
from repro.models import cnn
from repro.serving import ProgramRegistry, ServerConfig, build_server

HW, CH, BATCH, STAGES = 8, 3, 4, 2
NAME = "tiny-net"


def _program():
    m = W.CNNModel(NAME, HW, CH, (
        W.ConvLayer("c1", CH, 8, 3),
        W.ConvLayer("p1", 8, 8, 2, stride=2, kind="pool"),
        W.ConvLayer("fc", 8 * (HW // 2) ** 2, 10, 1, kind="fc"),
    ))
    p = cnn.init_params(m, jax.random.PRNGKey(0))
    calib = jax.random.normal(jax.random.PRNGKey(1), (2, HW, HW, CH))
    return compile_model(m, p, bits=8, calib_batch=calib)


def _server(replicas: int):
    reg = ProgramRegistry()
    reg.register(NAME, _program())
    frames = np.random.default_rng(2).standard_normal(
        (12, HW, HW, CH)).astype(np.float32)
    return build_server(reg, ServerConfig(
        batch=BATCH, stages=STAGES, replicas=replicas, max_wait_ms=500.0),
        streams={NAME: frames}), frames


def _serve_traced(replicas: int, log_dir: str, batches: int = 3):
    """Serve ``batches`` full batches with the profiler on; frames come
    one at a time, so the batcher blocks to fill each batch."""
    srv, frames = _server(replicas)
    try:
        jax.profiler.start_trace(log_dir)
        reqs = []
        for i in range(batches * BATCH):
            reqs.append(srv.submit(NAME, frames[i % len(frames)]))
            time.sleep(0.005)
        for r in reqs:
            r.result(timeout=120)
        time.sleep(0.3)          # the collector closes its last spans
        jax.profiler.stop_trace()
        stats = srv.stats()
    finally:
        srv.close()
    return _spans(log_dir), stats


def _spans(log_dir: str) -> list[tuple]:
    """``(name, start_ns, end_ns, thread, args)`` of each serve.* span."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for t, line in enumerate(plane.lines):
                out.extend((e.name, e.start_ns, e.end_ns, (plane.name, t),
                            dict(e.stats))
                           for e in line.events
                           if e.name.startswith("serve."))
    return out


def _inside(child, spans, parent: str) -> bool:
    """``child`` lies inside a ``parent`` span on its own thread."""
    return any(s[0] == parent and s[3] == child[3]
               and s[1] <= child[1] and child[2] <= s[2] for s in spans)


PARENT = {"serve.fill.wait": "serve.assemble",
          "serve.quantize": "serve.dispatch",
          "serve.enqueue.wait": "serve.dispatch",
          "serve.route": "serve.dispatch",
          "serve.stage.ready.wait": "serve.stage",
          "serve.dequantize": "serve.collect",
          "serve.deliver": "serve.collect"}
EXECUTOR = ("serve.quantize", "serve.enqueue.wait", "serve.stage",
            "serve.stage.ready.wait", "serve.handoff.wait", "serve.collect",
            "serve.dequantize", "serve.deliver")


def test_traced_server_marks_every_span_of_each_batch(tmp_path):
    spans, _ = _serve_traced(1, str(tmp_path))
    names = {s[0] for s in spans}
    assert names == {"serve.assemble", "serve.fill.wait", "serve.dispatch",
                     *EXECUTOR}
    for s in spans:
        if s[0] in PARENT:
            assert _inside(s, spans, PARENT[s[0]]), s
    batches = sorted(s[4]["batch"] for s in spans if s[0] == "serve.quantize")
    assert len(batches) == len(set(batches)) == 3
    assert sum(s[0] == "serve.dispatch" for s in spans) == 3
    # Every executor span carries the batch's number; per batch, one of
    # each host span and one of each stage span per stage.
    for b in batches:
        mine = [s for s in spans if s[4].get("batch") == b]
        count = {n: sum(s[0] == n for s in mine) for n in EXECUTOR}
        assert count == {"serve.quantize": 1, "serve.enqueue.wait": 1,
                         "serve.stage": STAGES,
                         "serve.stage.ready.wait": STAGES,
                         "serve.handoff.wait": STAGES, "serve.collect": 1,
                         "serve.dequantize": 1, "serve.deliver": 1}
        stages = sorted(s[4]["stage"] for s in mine if s[0] == "serve.stage")
        assert stages == list(range(STAGES))
    assert (sum(s[0] == "serve.stage" for s in spans)
            == len(batches) * STAGES)
    # Each stage runs on its own thread; the batcher is a third.
    threads = {s[4]["stage"]: s[3] for s in spans if s[0] == "serve.stage"}
    batcher = {s[3] for s in spans if s[0] == "serve.dispatch"}
    assert len(set(threads.values()) | batcher) == STAGES + 1


def test_replica_pool_marks_the_route_pick(tmp_path):
    spans, stats = _serve_traced(2, str(tmp_path), batches=2)
    routes = [s for s in spans if s[0] == "serve.route"]
    assert len(routes) == sum(s[0] == "serve.dispatch" for s in spans) == 2
    batches = sorted(s[4]["batch"] for s in routes)
    assert batches == [batches[0], batches[0] + 1]
    for s in routes:
        assert s[4]["replica"] in (0, 1)
        assert _inside(s, spans, "serve.dispatch")
    assert sum(s[0] == "serve.stage" for s in spans) == 2 * STAGES
    assert stats["models"][NAME]["stage_executables"] == [[1] * STAGES] * 2


@pytest.mark.parametrize("replicas", [1, 2])
def test_stats_count_one_executable_per_stage_after_warm_up(replicas):
    srv, _ = _server(replicas)
    try:
        row = srv.stats()["models"][NAME]
    finally:
        srv.close()
    assert row["stage_executables"] == [[1] * STAGES] * replicas


@pytest.mark.parametrize("replicas", [1, 2])
def test_stats_count_quantize_in_executables_and_batches(replicas):
    """``Server.stats()`` lists one quantize-in executable per replica
    and counts every batch that took the device quantize-in: each one
    the replicas' pipelines dispatched."""
    srv, frames = _server(replicas)
    try:
        before = srv.stats()["models"][NAME]
        for r in [srv.submit(NAME, frames[i]) for i in range(3 * BATCH)]:
            r.result(timeout=120)
        row = srv.stats()["models"][NAME]
        ex = srv.runtime(NAME).executor
        dispatched = sum(rep.stats.batches for rep in
                         getattr(ex, "replicas", None) or [ex])
    finally:
        srv.close()
    assert row["quantize_in_executables"] == [1] * replicas
    assert before["device_quantized_batches"] > 0      # the warm-up's
    assert row["device_quantized_batches"] - before[
        "device_quantized_batches"] >= 3
    assert row["device_quantized_batches"] >= dispatched >= 3


def _module_name(runner, x) -> str:
    text = runner.fn.lower(x, runner.weights).as_text()
    return re.search(r"module @(\w+)", text).group(1)


def test_stage_jit_names_are_distinct_per_stage_and_stable():
    prog = _program()
    cut = 1
    names = []
    for _ in range(2):
        first = prog.compile_stage_runner(0, cut)
        last = prog.compile_stage_runner(cut, len(prog.steps))
        x = first.quantize(np.zeros((BATCH, HW, HW, CH), np.float32))
        names.append([_module_name(first, x),
                      _module_name(last, first(x))])
    n = len(prog.steps)
    assert names[0] == names[1] == [f"jit_serve_tiny_net_0_{cut}",
                                    f"jit_serve_tiny_net_{cut}_{n}"]


def test_quantize_in_jit_has_a_fixed_name():
    """The first stage's quantize-in lowers to ``jit_quantize_in_<model>``
    with its ops under the ``quantize_in`` scope, the same in every
    runner."""
    prog = _program()
    flat = jnp.zeros((BATCH, HW * HW * CH), jnp.float32)
    for _ in range(2):
        text = prog.compile_stage_runner(0, 1).quantize_in.lower(
            flat, (0, 1, 2), (HW, HW, CH)).as_text(debug_info=True)
        assert re.search(r"module @(\w+)", text).group(1) == \
            "jit_quantize_in_tiny_net"
        assert "quantize_in/" in text


def test_pallas_kernel_has_a_fixed_name():
    from repro.kernels.conv2d_int8.kernel import gemm_int8
    x = jnp.ones((8, 16), jnp.int8)
    w = jnp.ones((16, 8), jnp.int8)
    jaxpr = jax.make_jaxpr(lambda x, w: gemm_int8(
        x, w, jnp.zeros((8,), jnp.int32), interpret=True))(x, w)
    (eqn,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert eqn.params["name"] == "gemm_int8"
