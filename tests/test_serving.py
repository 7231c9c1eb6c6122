"""Stage-pipelined serving subsystem: partition invariants, K-stage
bit-identity with the single-jit ``compile_runner`` chain (the acceptance
bar — including a stage boundary landing mid-conv-block and the K=1
degenerate case), thread-safe multi-producer execution, and the async
frontend's edge cases (empty stream, single frame, flush-by-timeout,
backpressure)."""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import workload as W
from repro.core.executor import EngineExecutor
from repro.core.program import compile_model
from repro.models import cnn
from repro.serving import (AsyncFrontend, PipelineExecutor,
                           partition_program, stage_devices, step_cycles)


def _tiny():
    """Small graph exercising every step kind: conv stem, pool, grouped
    conv, fc head (same shape as tests/test_executor.py's)."""
    m = W.CNNModel("tiny", 16, 4, (
        W.ConvLayer("c1", 4, 8, 3),
        W.ConvLayer("p1", 8, 8, 2, stride=2, kind="pool"),
        W.ConvLayer("c2", 8, 8, 3, groups=2),
        W.ConvLayer("fc", 8 * 8 * 8, 10, 1, kind="fc"),
    ))
    p = cnn.init_params(m, jax.random.PRNGKey(0))
    calib = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 4))
    prog = compile_model(m, p, bits=8, calib_batch=calib)
    frames = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                          (11, 16, 16, 4)), np.float32)
    return prog, frames


def _two_block():
    """Two conv *blocks* (conv-conv-pool twice) so a cut can land
    mid-block, between two convs that share a block."""
    m = W.CNNModel("twoblock", 16, 3, (
        W.ConvLayer("c1_1", 3, 8, 3),
        W.ConvLayer("c1_2", 8, 8, 3),
        W.ConvLayer("p1", 8, 8, 2, stride=2, kind="pool"),
        W.ConvLayer("c2_1", 8, 16, 3),
        W.ConvLayer("c2_2", 16, 16, 3),
        W.ConvLayer("p2", 16, 16, 2, stride=2, kind="pool"),
        W.ConvLayer("fc", 16 * 4 * 4, 10, 1, kind="fc"),
    ))
    p = cnn.init_params(m, jax.random.PRNGKey(3))
    calib = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 16, 3))
    prog = compile_model(m, p, bits=8, calib_batch=calib)
    frames = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                          (7, 16, 16, 3)), np.float32)
    return prog, frames


# ---------------------------------------------------------------------------
# Partition
# ---------------------------------------------------------------------------


def test_partition_invariants():
    """Contiguous cover, modeled cycles conserved, balance in (0, 1],
    bottleneck monotone non-increasing in K (more stages never model
    slower), pools never lead a stage."""
    prog, _ = _two_block()
    total = sum(step_cycles(prog.allocs).values())
    prev_bottleneck = float("inf")
    for k in range(1, 6):
        part = partition_program(prog, k)
        assert part.boundaries[0] == 0
        assert part.boundaries[-1] == len(prog.steps)
        assert list(part.boundaries) == sorted(set(part.boundaries))
        assert part.n_stages == k
        assert sum(part.stage_cycles) == pytest.approx(total)
        assert 0 < part.balance <= 1 + 1e-12
        assert part.bottleneck <= prev_bottleneck + 1e-9
        prev_bottleneck = part.bottleneck
        for b, e in part.stage_ranges()[1:]:
            assert prog.steps[b].kind != "pool"


def test_partition_rejects_bad_stage_counts():
    prog, _ = _tiny()
    with pytest.raises(ValueError):
        partition_program(prog, 0)
    with pytest.raises(ValueError):
        partition_program(prog, 4)  # only 3 compute steps
    plan_only = compile_model(W.CNN_MODELS["alexnet"](), theta=900, bits=8)
    with pytest.raises(ValueError):
        partition_program(plan_only, 2)


# ---------------------------------------------------------------------------
# Stage runners + pipelined bit-identity
# ---------------------------------------------------------------------------


def test_stage_runner_chain_bit_identical_all_routes():
    """Chaining compile_stage_runner ranges reproduces compile_runner
    exactly for every MAC lowering — int8 activations are the stage
    boundary contract."""
    prog, frames = _tiny()
    for route in ("f32", "oracle", "kernel"):
        full = prog.compile_runner(route=route)
        want = full.logits(frames[:4])
        first = prog.compile_stage_runner(0, 2, route=route)
        second = prog.compile_stage_runner(2, 4, route=route)
        mid = first(first.quantize(frames[:4]))
        assert np.asarray(mid).dtype == np.int8   # int8 across the cut
        got = second.dequantize(second(mid))
        np.testing.assert_array_equal(got, want)


def test_stage_runner_end_guards():
    """Host-side quantize/dequantize exist only at the matching chain
    ends; out-of-range stages are refused."""
    prog, frames = _tiny()
    inner = prog.compile_stage_runner(1, 3)
    with pytest.raises(ValueError):
        inner.quantize(frames[:1])
    with pytest.raises(ValueError):
        inner.dequantize(np.zeros((1, 10)))
    with pytest.raises(ValueError):
        prog.compile_stage_runner(2, 2)
    with pytest.raises(ValueError):
        prog.compile_stage_runner(0, 99)


@pytest.mark.parametrize("stages", [1, 2, 3])
def test_pipelined_bit_identical(stages):
    """K-stage pipelined serving == the single-jit chain, bit for bit,
    including the K=1 degenerate case and a padded tail batch."""
    prog, frames = _tiny()
    want = prog.compile_runner().logits(frames)
    with PipelineExecutor(prog, stages=stages, batch_size=4,
                          output="logits") as px:
        got = np.stack(px.serve(list(frames)))
    np.testing.assert_array_equal(got, want)
    assert px.stats.frames == len(frames)
    assert px.stats.padded_frames == 1
    # top1 path too
    with PipelineExecutor(prog, stages=stages, batch_size=4) as px:
        ids = px.serve(list(frames))
    np.testing.assert_array_equal(
        np.asarray(ids), np.argmax(want.reshape(len(frames), -1), -1))


def test_pipelined_mid_block_boundary_bit_identical():
    """A stage cut landing *inside* a conv block (between two convs that
    share a block, and one where a pool leads the next stage) stays
    bit-identical — the boundary contract is any step edge."""
    prog, frames = _two_block()
    want = prog.compile_runner().logits(frames)
    n = len(prog.steps)
    for bounds in [(0, 2, n),      # cut after c1_2 (mid-structure)
                   (0, 1, n),      # cut between c1_1 and c1_2: mid-block
                   (0, 4, n),      # cut between c2_1 and c2_2: mid-block
                   (0, 1, 4, n)]:  # both mid-block cuts at once
        with PipelineExecutor(prog, stages=len(bounds) - 1, batch_size=4,
                              boundaries=bounds, output="logits") as px:
            got = np.stack(px.serve(list(frames)))
        np.testing.assert_array_equal(got, want, err_msg=str(bounds))


def _tiny_residual():
    """``bench/tests/tiny_residual.json``'s graph (a strided stem, max
    pool, two bottlenecks with skip adds, average pool, fc) at 32x32x3,
    seeded weights; 10 frames."""
    import json
    from pathlib import Path

    from repro.compiler import import_source, quantize
    cfg = json.loads((Path(__file__).resolve().parents[1] / "bench" / "tests"
                      / "tiny_residual.json").read_text())
    model, _ = import_source(cfg["graph"])
    prog = quantize(model, bits=8, seed=5)
    frames = np.random.default_rng(5).standard_normal(
        (10, 32, 32, 3)).astype(np.float32)
    return prog, frames


def _host_quantized_logits(runners, frames, batch):
    """Logits as the host quantize-in served them: each batch padded
    with zero frames, ``quant.quantize_frames_np`` on the host, the int8
    batch chained through the stage runners."""
    from repro.core import quant
    out = []
    for i in range(0, len(frames), batch):
        chunk = list(frames[i:i + batch])
        n = len(chunk)
        chunk += [np.zeros_like(chunk[0])] * (batch - n)
        x = quant.quantize_frames_np(chunk, runners[0].program.e_input,
                                     runners[0].program.bits)
        for r in runners:
            x = r(x)
        out.append(runners[-1].dequantize(x)[:n])
    return np.concatenate(out)


@pytest.mark.parametrize("route", ["f32", "oracle", "kernel"])
@pytest.mark.parametrize("model", ["tiny", "tiny_residual"])
def test_served_logits_equal_host_quantized_chain(model, route):
    """The pipeline and ``Server`` serve, through the device quantize-in,
    the logits the host quantize-in gave: ``quantize_frames_np`` chained
    through the same stage runners, bit for bit, for a chain and a
    residual graph on every route; every served batch took the device
    quantize-in, compiled once."""
    from repro.serving import ProgramRegistry, ServerConfig, build_server
    prog, frames = _tiny() if model == "tiny" else _tiny_residual()
    with PipelineExecutor(prog, stages=2, batch_size=4, route=route,
                          output="logits") as px:
        got = np.stack(px.serve(list(frames)))
        want = _host_quantized_logits(px.runners, frames, 4)
    np.testing.assert_array_equal(got, want)
    assert px.runners[0].device_quantized_batches == 3
    assert px.runners[0].quantize_in_cache_size() == 1

    reg = ProgramRegistry()
    reg.register(model, prog)
    srv = build_server(reg, ServerConfig(batch=4, stages=2, route=route,
                                         output="logits", max_wait_ms=50.0),
                       streams={model: frames})
    try:
        got = np.stack([r.result(timeout=120) for r in
                        [srv.submit(model, f) for f in frames]])
        want = _host_quantized_logits(srv.runtime(model).executor.runners,
                                      frames, 4)
        row = srv.stats()["models"][model]
    finally:
        srv.close()
    np.testing.assert_array_equal(got, want)
    assert row["quantize_in_executables"] == [1]
    assert row["device_quantized_batches"] >= 3


def test_stage_devices_round_robin():
    """Placement policy: stage i -> devices[i % n], default jax.devices(),
    bad inputs refused."""
    devs = jax.devices()
    assert stage_devices(3) == [devs[i % len(devs)] for i in range(3)]
    fake = ["d0", "d1"]
    assert stage_devices(5, fake) == ["d0", "d1", "d0", "d1", "d0"]
    with pytest.raises(ValueError):
        stage_devices(0)
    with pytest.raises(ValueError):
        stage_devices(2, [])


@pytest.mark.parametrize("route", ["f32", "oracle", "kernel"])
def test_placed_stage_runners_bit_identical_all_routes(route):
    """--place-stages determinism: with every stage pinned to a device
    (all the same one on single-device CPU), K in {1, 2, 4} placed
    pipelines stay bit-identical to the monolithic compile_runner on
    every MAC route — placement moves buffers, never arithmetic."""
    prog, frames = _two_block()
    want = prog.compile_runner(route=route).logits(frames)
    for k in (1, 2, 4):
        with PipelineExecutor(prog, stages=k, batch_size=4, route=route,
                              place_stages=True, output="logits") as px:
            got = np.stack(px.serve(list(frames)))
        np.testing.assert_array_equal(got, want, err_msg=f"K={k}")
        assert len(px.stage_devices) == k
        assert all(d is not None for d in px.stage_devices)


def test_placed_runner_device_pin_single_runner():
    """compile_stage_runner(device=...) routes execution through the
    pinned device and stays bit-identical to the unpinned runner."""
    prog, frames = _tiny()
    dev = jax.devices()[0]
    pinned = prog.compile_stage_runner(0, len(prog.steps), device=dev)
    plain = prog.compile_runner()
    np.testing.assert_array_equal(pinned.logits(frames), plain.logits(frames))
    out = pinned(pinned.quantize(frames[:4]))
    assert next(iter(out.devices())) == dev


def test_pipeline_reuse_across_drains():
    """Workers survive drain(); a second stream through the same
    pipeline stays correct and never recompiles (fixed batch shape)."""
    prog, frames = _tiny()
    want = prog.compile_runner().logits(frames)
    with PipelineExecutor(prog, stages=2, batch_size=4,
                          output="logits") as px:
        got1 = np.stack(px.serve(list(frames)))
        got2 = np.stack(px.serve(list(frames[:5])))
        assert all(r.cache_size() == 1 for r in px.runners)
    np.testing.assert_array_equal(got1, want)
    np.testing.assert_array_equal(got2, want[:5])


def test_pipeline_rejects_bad_boundaries():
    prog, _ = _tiny()
    with pytest.raises(ValueError):
        PipelineExecutor(prog, stages=2, boundaries=(0, 4))       # wrong len
    with pytest.raises(ValueError):
        PipelineExecutor(prog, stages=2, boundaries=(1, 2, 4))    # no 0
    with pytest.raises(ValueError):
        PipelineExecutor(prog, stages=2, boundaries=(0, 2, 3))    # short


@pytest.mark.slow
@pytest.mark.parametrize("model,stages", [
    ("alexnet", 2), ("alexnet", 4), ("vgg16", 2), ("zf", 2), ("yolo", 2),
])
def test_pipelined_paper_models_bit_identical(model, stages):
    """The acceptance bar: K-stage pipelined output == compile_runner on
    all four paper CNNs (f32 route, int8 golden comparison on the raw
    logits)."""
    m = W.CNN_MODELS[model]()
    p = cnn.init_params(m, jax.random.PRNGKey(0))
    calib = jax.random.normal(jax.random.PRNGKey(1),
                              (1, m.input_hw, m.input_hw, m.input_ch))
    prog = compile_model(m, p, bits=8, calib_batch=calib)
    frames = np.asarray(jax.random.normal(
        jax.random.PRNGKey(2), (3, m.input_hw, m.input_hw, m.input_ch)),
        np.float32)
    want = prog.compile_runner(route="f32").logits(frames)
    with PipelineExecutor(prog, stages=stages, batch_size=2, route="f32",
                          output="logits") as px:
        got = np.stack(px.serve(list(frames)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Thread safety (the frontend's contract with EngineExecutor)
# ---------------------------------------------------------------------------


def _match_rows(got: np.ndarray, want: np.ndarray) -> None:
    """Every produced row must be exactly one expected row, each expected
    row consumed once (submission order across threads is arbitrary)."""
    assert got.shape == want.shape
    used = np.zeros(len(want), bool)
    for row in got:
        hit = np.nonzero((want == row).all(axis=1) & ~used)[0]
        assert hit.size > 0, "result row matches no unconsumed expectation"
        used[hit[0]] = True
    assert used.all()


def test_engine_executor_multi_producer_submit():
    """Concurrent submit() from several threads: no frame lost or
    corrupted through the shared pending buffer and tail padding."""
    prog, frames = _tiny()
    want = prog.compile_runner().logits(frames)
    ex = EngineExecutor(prog, batch_size=4, output="logits")
    chunks = [frames[0:3], frames[3:7], frames[7:11]]
    threads = [threading.Thread(target=ex.submit, args=(c,))
               for c in chunks]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = np.stack(ex.drain())
    _match_rows(got, want)
    assert ex.stats.frames == len(frames)


def test_pipeline_concurrent_submit_batch_own_logits():
    """Two threads call ``submit_batch`` at once with distinct frame
    lists (full and short batches): each tag's logits are its own
    frames' ``runner.logits``, bit for bit, so no quantize scratch or
    int8 batch is shared between producers."""
    import sys
    prog, _ = _tiny()
    runner = prog.compile_runner()
    rng = np.random.default_rng(7)
    work = {(t, k): list(rng.standard_normal((4 - k % 2, 16, 16, 4))
                         .astype(np.float32) * (t + 1))
            for t in range(2) for k in range(12)}
    got: dict = {}
    errors: list = []

    def producer(t):
        try:
            for k in range(12):
                px.submit_batch(work[t, k], len(work[t, k]), tag=(t, k))
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with PipelineExecutor(prog, stages=2, batch_size=4,
                              output="logits",
                              on_result=got.__setitem__) as px:
            threads = [threading.Thread(target=producer, args=(t,))
                       for t in range(2)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
                assert not th.is_alive()
            assert px.wait_idle(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    assert set(got) == set(work)
    for tag, frames in work.items():
        np.testing.assert_array_equal(np.stack(got[tag]),
                                      runner.logits(frames))


def test_frontend_over_engine_executor_multi_producer():
    """Many client threads -> AsyncFrontend -> thread-safe EngineExecutor:
    every request resolves to its own frame's exact logits."""
    prog, frames = _tiny()
    want = prog.compile_runner().logits(frames)
    ex = EngineExecutor(prog, batch_size=4, output="logits")
    fe = AsyncFrontend(ex, max_wait_ms=30.0)
    results = [None] * len(frames)

    def client(i):
        results[i] = fe.submit(frames[i]).result(timeout=120)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(frames))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fe.close()
    for i, r in enumerate(results):
        np.testing.assert_array_equal(np.asarray(r), want[i])
    assert fe.stats.completed == len(frames)
    assert not np.isnan(fe.stats.latency_percentiles()["p99"])


# ---------------------------------------------------------------------------
# Frontend edge cases
# ---------------------------------------------------------------------------


def test_frontend_empty_stream():
    """Close with zero submissions: no hang, clean stats, submit-after-
    close refused."""
    prog, _ = _tiny()
    with PipelineExecutor(prog, stages=2, batch_size=4) as px:
        fe = AsyncFrontend(px)
        fe.close()
        assert fe.stats.submitted == 0
        assert fe.stats.completed == 0
        assert fe.stats.fps == 0.0
        assert np.isnan(fe.stats.latency_percentiles()["p50"])
        with pytest.raises(RuntimeError):
            fe.submit(np.zeros((16, 16, 4), np.float32))


def test_frontend_single_frame_flush_by_timeout():
    """One lone frame must be answered after ~max_wait_ms, not parked
    waiting for a full batch."""
    prog, frames = _tiny()
    want = prog.compile_runner().logits(frames[:1])
    with PipelineExecutor(prog, stages=2, batch_size=4,
                          output="logits") as px:
        px.serve(list(frames[:4]))          # warm the stage jits
        fe = AsyncFrontend(px, max_wait_ms=10.0)
        req = fe.submit(frames[0])
        out = req.result(timeout=60)
        fe.close()
    np.testing.assert_array_equal(out, want[0])
    assert fe.stats.flushes_timeout == 1
    assert fe.stats.flushes_full == 0
    assert req.latency_s is not None and req.latency_s >= 0.010 * 0.5


def test_frontend_backpressure_bounded_queue():
    """A full submission queue blocks, and queue.Full surfaces when the
    caller's timeout expires (stub executor that never completes until
    released, so the test is deterministic)."""
    import queue as queue_mod

    release = threading.Event()

    class StallExecutor:
        batch_size = 2
        program = None
        on_result = None
        on_error = None

        def submit_batch(self, frames, n_valid, tag=None):
            release.wait(timeout=30)
            if self.on_result:
                self.on_result(tag, np.zeros((n_valid, 1)))

        def flush_inflight(self):
            pass

        def reset_stats(self):
            pass

        def replica_counts(self):
            return None

    ex = StallExecutor()
    fe = AsyncFrontend(ex, max_wait_ms=5.0, max_queue=2)
    f = np.zeros((4, 4, 1), np.float32)
    reqs = [fe.submit(f) for f in [f] * 2]      # first batch stalls
    time.sleep(0.05)                             # batcher picks them up
    reqs += [fe.submit(f) for f in [f] * 2]      # fills the queue
    with pytest.raises(queue_mod.Full):
        fe.submit(f, timeout=0.05)
    release.set()
    for r in reqs:
        r.result(timeout=30)
    fe.close()
    assert fe.stats.completed == fe.stats.submitted == 4


def test_frontend_resolves_requests_on_executor_failure():
    """A dispatch failure must resolve that batch's requests with the
    error (not kill the batcher silently): result() raises, close()
    converges, later submits still get answers."""
    class BrokenExecutor:
        batch_size = 2
        program = None
        on_result = None
        on_error = None

        def submit_batch(self, frames, n_valid, tag=None):
            raise RuntimeError("stage worker died")

        def flush_inflight(self):
            pass

        def reset_stats(self):
            pass

        def replica_counts(self):
            return None

    fe = AsyncFrontend(BrokenExecutor(), max_wait_ms=5.0)
    f = np.zeros((4, 4, 1), np.float32)
    reqs = [fe.submit(f) for _ in range(3)]
    for r in reqs:
        with pytest.raises(RuntimeError):
            r.result(timeout=30)
    fe.close()
    assert fe.stats.failed == 3
    assert fe.stats.completed == 0


def test_frontend_rejects_malformed_frame_at_submit():
    """A wrong-shape frame is refused at the client, before it can
    poison a micro-batch inside the batcher thread."""
    prog, frames = _tiny()
    with PipelineExecutor(prog, stages=1, batch_size=4) as px:
        fe = AsyncFrontend(px, max_wait_ms=10.0)
        with pytest.raises(ValueError):
            fe.submit(np.zeros((8, 8, 4), np.float32))
        req = fe.submit(frames[0])
        req.result(timeout=60)
        fe.close()
    assert fe.stats.completed == 1


def test_frontend_stage_failure_resolves_requests():
    """A stage worker dying mid-batch must deliver the error to that
    batch's requests through on_error — futures never hang."""
    prog, frames = _tiny()
    px = PipelineExecutor(prog, stages=2, batch_size=4)

    def boom(xq):
        raise RuntimeError("stage exploded")

    px.runners[0] = dataclasses.replace(px.runners[0], fn=boom)
    with px:
        fe = AsyncFrontend(px, max_wait_ms=5.0)
        req = fe.submit(frames[0])
        with pytest.raises(RuntimeError):
            req.result(timeout=60)
        fe.close()                      # converges: the request resolved
    assert fe.stats.failed == 1
    assert fe.stats.completed == 0


def test_frontend_rejects_busy_executor_until_closed():
    """A second frontend on a busy executor is refused; after close()
    the executor is released and reusable."""
    prog, frames = _tiny()
    with PipelineExecutor(prog, stages=1, batch_size=4,
                          output="logits") as px:
        fe = AsyncFrontend(px)
        with pytest.raises(ValueError):
            AsyncFrontend(px)           # on_result already consumed
        fe.close()
        fe2 = AsyncFrontend(px)         # released on close
        want = prog.compile_runner().logits(frames[:1])
        got = fe2.submit(frames[0]).result(timeout=120)
        fe2.close()
    np.testing.assert_array_equal(got, want[0])
