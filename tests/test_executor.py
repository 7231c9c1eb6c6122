"""Jitted batched executor: bit-identity with the eager per-sample path
(all routes), streaming micro-batch semantics, recompile/donation guards,
and the YOLO/ZF golden int8 outputs."""

import dataclasses
import importlib.util
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import workload as W
from repro.core.executor import EngineExecutor
from repro.core.program import compile_model
from repro.models import cnn

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


def _tiny():
    """Small graph exercising every step kind: strided conv stem, pool,
    grouped conv, fc head."""
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


def _eager(prog, frames, **kw):
    return np.concatenate([np.asarray(prog.run(frames[i:i + 1], **kw))
                           for i in range(len(frames))])


@pytest.mark.parametrize("route", ["f32", "oracle", "kernel"])
def test_runner_routes_bit_identical_to_eager(route):
    """One jitted chain == the eager per-step loop, for every MAC
    lowering (exact-f32 chunked conv, int32 oracle, Pallas kernel)."""
    prog, frames = _tiny()
    want = _eager(prog, frames)
    runner = prog.compile_runner(route=route)
    got = runner.logits(frames)
    np.testing.assert_array_equal(got, want)
    assert runner.cache_size() == 1


def test_executor_stream_matches_eager():
    """submit/drain over a non-multiple frame count: order preserved,
    padding dropped, outputs bit-identical, stats consistent."""
    prog, frames = _tiny()
    want = _eager(prog, frames)
    ex = EngineExecutor(prog, batch_size=4, output="logits")
    got = np.stack(ex.serve(list(frames)))
    np.testing.assert_array_equal(got, want)
    assert ex.stats.frames == 11
    assert ex.stats.batches == 3
    assert ex.stats.padded_frames == 1
    ids = EngineExecutor(prog, batch_size=4).serve(list(frames))
    np.testing.assert_array_equal(
        np.asarray(ids), np.argmax(want.reshape(len(frames), -1), -1))


def test_executor_never_recompiles():
    """Tail padding keeps the batch shape fixed: one XLA executable no
    matter how many (partial) micro-batches stream through."""
    prog, frames = _tiny()
    ex = EngineExecutor(prog, batch_size=4)
    ex.serve(list(frames))          # 2 full batches + padded tail
    ex.submit(frames[:3])           # reuse across drains, partial again
    ex.drain()
    assert ex.runner.cache_size() == 1


def test_donated_runner_still_correct():
    """Forcing donation must not change results (CPU ignores the donation
    with a warning; on TPU the int8 buffer is actually reused)."""
    prog, frames = _tiny()
    want = _eager(prog, frames[:4])
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # "donated buffers were not usable"
        runner = prog.compile_runner(route="f32", donate=True)
        got = runner.logits(frames[:4])
        got2 = runner.logits(frames[:4])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got2, want)
    assert runner.cache_size() == 1


def test_kernel_route_checked_up_front():
    """A kernel request that cannot run raises at compile/jit time — no
    silent per-step fallback to the oracle."""
    m = W.CNNModel("tiny16", 8, 3, (W.ConvLayer("c1", 3, 4, 3),))
    p = cnn.init_params(m, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 3))
    prog = compile_model(m, p, bits=16, calib_batch=x)
    with pytest.raises(NotImplementedError):
        prog.compile_runner(route="kernel")
    with pytest.raises(NotImplementedError):
        prog.run(x, use_kernel=True)
    with pytest.raises(NotImplementedError):
        cnn.forward(p, m, x, quantized=True, bits=16, use_kernel=True)
    with pytest.raises(NotImplementedError):
        prog.compile_runner(route="f32")   # exact-f32 needs int8 products
    assert prog.compile_runner().route == "oracle"


def test_f32_route_refuses_oversized_kernel():
    """The exact-f32 proof needs R*S <= 1024 per chunk; a >32x32 kernel
    must be refused at compile time, not silently lose bits."""
    m = W.CNNModel("bigk", 40, 1, (W.ConvLayer("c1", 1, 2, 33),))
    p = cnn.init_params(m, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 40, 1))
    prog = compile_model(m, p, bits=8, calib_batch=x)
    with pytest.raises(NotImplementedError):
        prog.compile_runner(route="f32")
    got = prog.compile_runner(route="oracle").logits(np.asarray(x))
    np.testing.assert_array_equal(got, np.asarray(prog.run(x)))


def test_stats_exclude_idle_between_drains():
    """wall_s accumulates active serving windows only — host idle between
    a drain and the next submit must not dilute steady_fps."""
    import time
    prog, frames = _tiny()
    ex = EngineExecutor(prog, batch_size=4)
    ex.serve(list(frames[:4]))
    w1 = ex.stats.wall_s
    time.sleep(0.25)
    t0 = time.perf_counter()
    ex.serve(list(frames[4:8]))
    window = time.perf_counter() - t0
    assert ex.stats.frames == 8
    # The recorded wall time may grow by at most the measured active
    # serve window — never by the idle sleep before it. Bounding against
    # the measurement (not a fixed constant) keeps this stable on slow
    # CI runners.
    assert ex.stats.wall_s - w1 <= window + 0.05


def test_plan_only_program_cannot_build_runner():
    prog = compile_model(W.CNN_MODELS["alexnet"](), theta=900, bits=8)
    with pytest.raises(ValueError):
        prog.compile_runner()


@pytest.mark.slow
@pytest.mark.parametrize("model", ["alexnet", "vgg16"])
def test_batched_matches_eager_paper_models(model):
    """Batched jitted runner == eager per-sample loop on the real paper
    models (f32 route; AlexNet additionally pins the kernel route)."""
    m = W.CNN_MODELS[model]()
    p = cnn.init_params(m, jax.random.PRNGKey(0))
    calib = jax.random.normal(jax.random.PRNGKey(1),
                              (1, m.input_hw, m.input_hw, m.input_ch))
    prog = compile_model(m, p, bits=8, calib_batch=calib)
    frames = np.asarray(jax.random.normal(
        jax.random.PRNGKey(2), (2, m.input_hw, m.input_hw, m.input_ch)),
        np.float32)
    want = _eager(prog, frames)
    got = prog.compile_runner(route="f32").logits(frames)
    np.testing.assert_array_equal(got, want)
    if model == "alexnet":
        got_k = prog.compile_runner(route="kernel").logits(frames)
        np.testing.assert_array_equal(got_k, want)


@pytest.mark.slow
@pytest.mark.parametrize("model", ["zf", "yolo"])
def test_golden_int8_program(model):
    """YOLO and ZF bit-exact against checked-in goldens (ROADMAP item):
    raw int32 accumulators (sample + crc of the full buffer), top-1 ids,
    and the frozen exponent schedule; frame 0 cross-checked against the
    eager oracle."""
    spec = importlib.util.spec_from_file_location(
        "golden_generate", os.path.join(GOLDEN_DIR, "generate.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    got = gen.golden_for(model)
    want = np.load(os.path.join(GOLDEN_DIR, f"{model}.npz"))
    np.testing.assert_array_equal(got["e_out"], want["e_out"])
    assert int(got["e_input"]) == int(want["e_input"])
    np.testing.assert_array_equal(got["acc_sample"], want["acc_sample"])
    np.testing.assert_array_equal(got["top1"], want["top1"])
    assert int(got["acc_crc"]) == int(want["acc_crc"])
    # and the jitted batched path == the eager oracle on the same program
    from repro.compiler import golden_frames
    from repro.serving.server import compile_for_serving
    prog = compile_for_serving(model)
    frame = golden_frames(prog.model)[:1]
    y_eager = np.asarray(prog.run(frame))
    runner = prog.compile_runner(route="f32")
    acc0 = np.asarray(runner(runner.quantize(frame)))
    np.testing.assert_array_equal(runner.dequantize(acc0), y_eager)
    crc_full = zlib.crc32(np.ascontiguousarray(acc0).tobytes())
    assert acc0.dtype == np.int32 and crc_full != 0


def test_quantize_np_twin_bit_identical():
    """Host-side numpy quantize == the jnp compile-time quantize,
    including round-half-to-even ties and rail clipping."""
    from repro.core import quant
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 7, 7, 5)).astype(np.float32) * 40
    x.reshape(-1)[:8] = [0.5, 1.5, 2.5, -0.5, -1.5, 300.0, -300.0, 0.0]
    for e in (-3, 0, 2):
        for bits in (8, 16):
            a = np.asarray(quant.quantize_to_exponent(jnp.asarray(x), e,
                                                      bits))
            b = quant.quantize_to_exponent_np(x, e, bits)
            np.testing.assert_array_equal(a, b)
            assert b.dtype == (np.int8 if bits == 8 else np.int16)


def _planted_frames(dtype, e, bits, n, layout):
    """``n`` frames ``[7, 6, 5]`` of ``dtype``, float ones carrying the
    ties and rails of the twin test plus ties and rails at the scale
    ``2^e`` sets. ``layout``: ``contiguous``; ``strided`` (every other
    row of a larger array); ``permuted`` (the frames interleaved in
    memory and their channels outside their columns, as a frame pool
    fetched from a device can be)."""
    rng = np.random.default_rng(n)
    if np.issubdtype(dtype, np.integer):
        x = rng.integers(0, 256, (n, 7, 6, 5)).astype(dtype)
    else:
        x = rng.standard_normal((n, 7, 6, 5)) * 40
        qmax = 2 ** (bits - 1) - 1
        scaled = np.array([0.5, 2.5, -1.5, 127.5, -127.5, 128.5, -128.5,
                           qmax + 0.5, -qmax - 0.5, -qmax - 1.5])
        planted = np.concatenate([
            [0.5, 1.5, 2.5, -0.5, -1.5, 300.0, -300.0, 0.0],
            scaled * 2.0 ** e])
        x.reshape(n, -1)[:, :len(planted)] = planted
        x = x.astype(dtype)
    if layout == "strided":
        big = np.zeros((n, 14, 6, 5), dtype)
        big[:, ::2] = x
        return big[:, ::2]
    if layout == "permuted":
        return np.ascontiguousarray(x.transpose(1, 3, 0, 2)).transpose(
            2, 0, 3, 1)
    return x


@pytest.mark.parametrize("layout", ["contiguous", "strided", "permuted"])
@pytest.mark.parametrize("form", ["list", "array"])
@pytest.mark.parametrize("n", [1, 5, 8])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.uint8])
def test_quantize_frames_np_equals_padded_stack(dtype, n, form, layout):
    """The quantize of frames straight into the int8 batch == stacking
    the frames, zero-padding to the batch and quantizing the whole
    batch: for every input dtype and memory layout, a sequence or an
    array, short batches padded with a shared zero frame (as
    ``pad_micro_batch`` pads them) and full ones, and every exponent and
    width. The batch keeps the memory order ``np.stack`` gives the
    frames."""
    from repro.core import quant
    for e in (-3, 0, 2):
        for bits in (8, 16):
            x = _planted_frames(dtype, e, bits, n, layout)
            stacked = np.stack(list(x))
            got = quant.quantize_frames_np(list(x) if form == "list" else x,
                                           e, bits)
            want = quant.quantize_to_exponent_np(stacked, e, bits)
            assert got.dtype == want.dtype == (np.int8 if bits == 8
                                               else np.int16)
            np.testing.assert_array_equal(got, want)
            assert (np.argsort(got.strides).tolist()
                    == np.argsort(stacked.strides).tolist())
            zero = np.zeros(x.shape[1:], np.float32)
            padded = quant.quantize_frames_np(
                [*x, *[zero] * (8 - n)], e, bits)
            np.testing.assert_array_equal(
                padded, quant.quantize_to_exponent_np(
                    np.concatenate([stacked, np.zeros(
                        (8 - n,) + x.shape[1:], stacked.dtype)]), e, bits))


@pytest.fixture(scope="module")
def quantize_in_runners():
    """First-stage runners of ``_tiny``'s program, pinned to the first
    device, one per input exponent and width, made on demand and shared
    by the cases below (so each frame order and batch compiles once)."""
    prog, _ = _tiny()
    runners = {}

    def get(e, bits):
        if (e, bits) not in runners:
            runners[e, bits] = dataclasses.replace(
                prog, e_input=e, bits=bits).compile_stage_runner(
                    0, 1, device=jax.devices()[0])
        return runners[e, bits]
    return get


@pytest.mark.parametrize("layout", ["contiguous", "strided", "permuted"])
@pytest.mark.parametrize("form", ["list", "array"])
@pytest.mark.parametrize("n", [1, 5, 8])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.uint8])
def test_device_quantize_in_equals_host_quantize(quantize_in_runners, dtype,
                                                 n, form, layout):
    """``CompiledRunner.quantize`` (one copy in the frames' own order, a
    flat transfer, the jitted quantize-in) == ``quant.quantize_frames_np``
    bit for bit, as a fresh array on the runner's device: for every input
    dtype and memory layout, a sequence or an array, short batches padded
    to 8 with a shared zero frame and full ones, every exponent and
    width, with ties and rails planted."""
    from repro.core import quant
    for e in (-3, 0, 2):
        for bits in (8, 16):
            runner = quantize_in_runners(e, bits)
            x = _planted_frames(dtype, e, bits, n, layout)
            zero = np.zeros(x.shape[1:], np.float32)
            for frames in (list(x) if form == "list" else x,
                           [*x, *[zero] * (8 - n)]):
                got = runner.quantize(frames)
                want = quant.quantize_frames_np(frames, e, bits)
                assert isinstance(got, jax.Array)
                assert got.devices() == {jax.devices()[0]}
                assert got.dtype == want.dtype
                np.testing.assert_array_equal(np.asarray(got), want)


def test_quantize_in_compiles_once_per_frame_order():
    """The quantize-in compiles once for frames of one memory order,
    lists and arrays, full and padded alike, and once more for a second
    order; each call counts one device-quantized batch."""
    from repro.core.executor import pad_micro_batch
    prog, frames = _tiny()
    runner = prog.compile_runner(route="f32")
    assert runner.quantize_in_cache_size() == 0
    runner.quantize(frames[:8])
    runner.quantize(list(frames[3:]))
    runner.quantize(pad_micro_batch(prog, frames[:3], 8))
    assert runner.quantize_in_cache_size() == 1
    permuted = np.ascontiguousarray(
        frames[:8].transpose(1, 3, 0, 2)).transpose(2, 0, 3, 1)
    runner.quantize(permuted)
    runner.quantize(list(permuted))
    assert runner.quantize_in_cache_size() == 2
    assert runner.device_quantized_batches == 5


def test_donating_runner_copies_a_callers_array_and_donates_its_own():
    """With donation on, a device array the caller passes is copied
    first and stays valid; one handed over as ``owned`` (the quantize-in's
    fresh output) is donated as it is. The stage keeps its input's shape,
    so the donation is usable, on a CPU device too."""
    import warnings
    prog, _ = _tiny()
    mid = prog.compile_stage_runner(2, 3, route="f32", donate=True)
    x = jnp.asarray(np.random.default_rng(3).integers(
        -128, 128, (4, 8, 8, 8)), jnp.int8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # "donated buffers were not usable"
        want = np.asarray(mid(x))
        assert not x.is_deleted()
        np.testing.assert_array_equal(np.asarray(mid(x)), want)
        mine = jnp.array(x, copy=True)
        np.testing.assert_array_equal(np.asarray(mid(mine, owned=True)),
                                      want)
    assert mine.is_deleted() and not x.is_deleted()
    assert mid.cache_size() == 1


def test_quantize_frames_np_refuses_bad_frames():
    """Frames of unequal shape and an empty sequence are refused, not
    broadcast."""
    from repro.core import quant
    frames = [np.zeros((4, 4, 3), np.float32)] * 3
    with pytest.raises(ValueError, match="shape"):
        quant.quantize_frames_np(frames + [np.zeros((1, 4, 3))], 0, 8)
    with pytest.raises(ValueError, match="no frames"):
        quant.quantize_frames_np([], 0, 8)


def test_runner_quantize_list_equals_array():
    """``CompiledRunner.quantize`` of a frame list == of the stacked
    array; a short batch padded by ``pad_micro_batch`` (one shared zero
    frame, no stack) quantizes to the same rows and zero rows after."""
    from repro.core.executor import pad_micro_batch
    prog, frames = _tiny()
    runner = prog.compile_runner(route="f32")
    np.testing.assert_array_equal(runner.quantize(list(frames)),
                                  runner.quantize(frames))
    padded = pad_micro_batch(prog, frames[:3], 8)
    assert len(padded) == 8 and not isinstance(padded, np.ndarray)
    got = runner.quantize(padded)
    np.testing.assert_array_equal(got[:3], runner.quantize(frames[:3]))
    assert got.shape == (8,) + frames.shape[1:] and not got[3:].any()
    with pytest.raises(ValueError, match="exceeds"):
        pad_micro_batch(prog, frames, 8)
    with pytest.raises(ValueError, match="does not match"):
        pad_micro_batch(prog, [frames[0][:, :8]], 8)
