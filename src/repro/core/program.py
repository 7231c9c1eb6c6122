"""Compiled engine programs: one plan drives execution, simulation and
benchmarks.

The paper's central object is a *balanced plan*: per-layer workloads
(Section 3), the multiplier/buffer allocation that balances them
(Algorithms 1/2), and the fixed-point formats the engines exchange
(Fig. 3(c)). :func:`compile_model` materializes that plan once as an
:class:`EngineProgram`:

1. **allocate** — Algorithms 1 and 2 run once over the model's
   :class:`~repro.core.workload.LayerWorkload` graph, producing the
   per-engine ``LayerAlloc``s every consumer shares (``program.allocs``
   feeds ``simulator.simulate`` and the throughput model directly).
2. **calibrate** — a float forward over ``calib_batch`` records per-layer
   activation ranges; per-tensor activation exponents and per-output-channel
   weight exponents are frozen, weights are quantized *once* (int8 + a shift
   schedule), and biases are pre-scaled onto each engine's 32-bit
   accumulator format.
3. **lower** — each layer becomes an :class:`EngineStep` whose bias-add,
   ReLU and requantize-to-int8 are fused into the GEMM epilogue
   (`kernels/conv2d_int8`), so activations stay int8 end-to-end: no
   per-forward ``quantize_po2``, no float32 bounce between layers.

``run(x)`` executes the program either through the Pallas PE-array kernels
(``use_kernel=True``; interpret mode on CPU) or through a pure-jnp integer
oracle — the two are bit-identical, which is what ``tests/test_program.py``
pins down.

Calibration and quantization run on the host CPU device
(:func:`host_device`), so the compiled integers are the same whichever
accelerator later runs the MACs, and the goldens in ``tests/golden/``
hold for every platform.

For serving, :meth:`EngineProgram.compile_runner` lowers the *whole* step
chain into one ``jax.jit``-compiled function (weights, bias and shift
schedules passed as arguments that live on the runner's device, the int8
activation buffer donated), so a stream of frames runs as a single fused
device program instead of the eager per-step loop — the software analogue
of switching the paper's engines from frame-at-a-time operation to the
steady-state pipeline.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.allocator import (LayerAlloc, allocate_buffers,
                                  allocate_compute)
from repro.core.workload import COMPUTE, INPUT, CNNModel, ConvLayer

Params = dict[str, Any]

# ZC706-class board defaults (the paper's Table I setting).
DEFAULT_THETA = 900
DEFAULT_BRAM = 1090
DEFAULT_BW = 4.2e9
DEFAULT_FREQ = 200e6


def host_device():
    """The host CPU device. Parameter draws, calibration and quantization
    run here: an accelerator's float arithmetic (bf16 matmul passes,
    different transcendental code) could move a rounding boundary and
    change the compiled integers."""
    return jax.devices("cpu")[0]


# ---------------------------------------------------------------------------
# Shared float executor (the calibration reference and the fp32 model path)
# ---------------------------------------------------------------------------


def float_forward(params: Params, model: CNNModel, x: jnp.ndarray,
                  record: dict[str, float] | None = None) -> jnp.ndarray:
    """Reference float forward over the model graph (NHWC): the last
    layer's output. With ``record`` it doubles as the calibration pass:
    the output amax of each conv, fc and add (after its ReLU, where one
    folds into it: what the next layers actually consume) is stored under
    the layer name, the network input under ``"__input__"``."""
    if record is not None:
        record["__input__"] = float(jnp.max(jnp.abs(x)))
    env = {INPUT: x}
    for lyr, src, hw in zip(model.layers, model.sources(), model.in_hws()):
        x = env[src[0]]
        if lyr.kind in ("pool", "avgpool"):
            lo, hi = lyr.padding(hw)
            win = ((1, lyr.kernel, lyr.kernel, 1),
                   (1, lyr.stride, lyr.stride, 1),
                   ((0, 0), (lo, hi), (lo, hi), (0, 0)))
            if lyr.kind == "pool":
                x = -jax.lax.reduce_window(-x, jnp.inf, jax.lax.min, *win)
            else:
                x = jax.lax.reduce_window(x, np.float32(0), jax.lax.add,
                                          *win) / np.float32(lyr.kernel ** 2)
        else:
            if lyr.kind == "add":
                x = x + env[src[1]]
            elif lyr.kind == "fc":
                w, b = params[lyr.name]["w"], params[lyr.name]["b"]
                x = x.reshape(x.shape[0], -1) @ w + b
            else:
                w, b = params[lyr.name]["w"], params[lyr.name]["b"]
                lo, hi = lyr.padding(hw)
                x = jax.lax.conv_general_dilated(
                    x, w, (lyr.stride, lyr.stride), ((lo, hi), (lo, hi)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    feature_group_count=lyr.groups) + b
            if model.has_relu(lyr):
                x = jax.nn.relu(x)
            if record is not None:
                record[lyr.name] = float(jnp.max(jnp.abs(x)))
        env[lyr.name] = x
    return x


# ---------------------------------------------------------------------------
# Lowered steps
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineStep:
    """One pipeline engine, fully lowered: quantized weights, the frozen
    shift schedule, and the spatial plumbing the kernel needs."""

    name: str
    kind: str                      # "conv" | "fc" | "pool" | "avgpool" | "add"
    layer: ConvLayer
    pad: tuple[int, int]           # (lo, hi), both spatial dims
    inputs: tuple[str, ...] = (INPUT,)     # the tensors it reads, by name
    # compute-step payload (None for the others), host numpy arrays; a
    # runner puts them on its device once:
    wq: np.ndarray | None = None           # int8/int16 quantized weights
    bias_q: np.ndarray | None = None       # int32 bias on the acc format
    # int32 [M]: e_out - (e_in+e_w); an add's one shift, e_out - e_in
    shift: np.ndarray | None = None
    e_in: int = 0                          # input activation exponent
    e_w: np.ndarray | None = None          # int [M] weight exponents
    e_out: int = 0                         # output activation exponent
    relu: bool = False
    requantize: bool = True        # False on the last engine (emit acc32)
    align: tuple[int, int] = (0, 0)        # add: left shifts to e_in


@dataclasses.dataclass
class EngineProgram:
    """The compiled plan. ``allocs`` is the single source of truth for
    cycles (simulator / throughput model / Table I); ``steps`` is the
    executable lowering of the same layers."""

    model: CNNModel
    bits: int
    theta_total: int
    allocs: list[LayerAlloc]
    steps: list[EngineStep] | None = None
    e_input: int = 0
    freq_hz: float = DEFAULT_FREQ

    # -- analytics ----------------------------------------------------------

    @property
    def gop(self) -> float:
        return self.model.gop

    def frame_cycles(self) -> float:
        from repro.core import throughput as T
        return T.frame_cycles(self.allocs)

    def fps(self) -> float:
        from repro.core import throughput as T
        return T.pipeline_fps(self.allocs, freq_hz=self.freq_hz)

    # -- execution ----------------------------------------------------------

    def out_scale(self) -> np.ndarray:
        """Per-channel float32 po2 scale of the output layer's int32
        accumulators (logits = acc * out_scale, exactly)."""
        out = next(s for s in self.steps if s.name == self.model.output.name)
        return np.exp2(np.asarray(out.e_in + out.e_w, np.float32))

    def skip_across(self, cut: int) -> str | None:
        """The first tensor a stage boundary before step ``cut`` would
        split: made before ``cut`` (the network input counts), read at or
        after it, and not step ``cut - 1``'s output, the one tensor a
        boundary carries. None where the cut is legal."""
        src = self.steps[cut - 1].name if cut else INPUT
        made = {INPUT} | {s.name for s in self.steps[:cut]}
        return next((n for s in self.steps[cut:] for n in s.inputs
                     if n in made and n != src), None)

    def run(self, x: jnp.ndarray, *, use_kernel: bool = False) -> jnp.ndarray:
        """Fixed-point forward, eagerly step by step. ``x`` is float NHWC;
        returns float logits (the final engine's 32-bit accumulators on
        their exact po2 scale). All intermediate activations are int8
        (int16 for bits=16). This is the per-sample reference path; for
        throughput use :meth:`compile_runner`."""
        if self.steps is None:
            raise ValueError(
                "plan-only program (compiled without params) cannot run")
        if use_kernel:
            require_kernel(self.bits)
            interpret = _kernel_interpret(jax.devices()[0])
        xq = quant.quantize_to_exponent(x, self.e_input, self.bits)
        if use_kernel:
            def mac(xq, step):
                return _step_kernel(xq, step, interpret)
        else:
            def mac(xq, step):
                return _step_oracle(xq, step, self.bits)
        xq = _walk(self.steps, xq, INPUT, mac, self.bits)
        scale = jnp.asarray(self.out_scale())
        return xq.astype(jnp.float32) \
            * scale.reshape((1,) * (xq.ndim - 1) + (-1,))

    def _resolve_route(self, route: str | None,
                       steps: tuple[EngineStep, ...]) -> str:
        """Validate a MAC-route request against ``steps`` (shared by the
        whole-chain and stage runners so a stage cannot silently accept a
        lowering the full chain would refuse)."""
        if route is None:
            route = "oracle" if self.bits > 8 else "f32"
        if route not in ("f32", "oracle", "kernel"):
            raise ValueError(f"unknown route {route!r}")
        if route == "kernel":
            require_kernel(self.bits)
        if route == "f32" and self.bits > 8:
            raise NotImplementedError(
                "the exact-f32 route holds only for int8 products "
                "(<= 2^14 per MAC); bits=16 uses route='oracle'")
        if route == "f32":
            # The exactness proof chunks the reduction over channels; a
            # single (r, s) tap plane is its floor. Kernels wider than
            # 32x32 (none in the paper's models) would overflow 2^24
            # within one chunk — refuse rather than silently lose bits.
            for s in steps:
                if s.kind == "conv" and \
                        s.layer.kernel ** 2 > _F32_CHUNK_MACS:
                    raise NotImplementedError(
                        f"step {s.name}: {s.layer.kernel}x"
                        f"{s.layer.kernel} kernel exceeds the exact-f32 "
                        f"chunk bound ({_F32_CHUNK_MACS} MACs); use "
                        f"route='oracle'")
        return route

    def compile_runner(self, *, route: str | None = None,
                       donate: bool | None = None) -> "CompiledRunner":
        """Lower the whole step chain into ONE jitted function over a batch
        of already-quantized frames and wrap it as a :class:`CompiledRunner`.

        ``route`` selects the MAC lowering (every route computes the exact
        same integers — pinned by ``tests/test_executor.py``):

        * ``"f32"`` (default for bits=8) — the int8 MACs run as chunked
          float32 convolutions/GEMMs: each partial sum accumulates at most
          1024 products of magnitude <= 2^14, so every intermediate is an
          integer <= 2^24 and float32 arithmetic is *bit-exact* (MACs are
          pinned to ``Precision.HIGHEST`` so GPU TF32 / TPU bf16 lowering
          cannot degrade them — see :func:`_step_exact_f32`). This hits
          the backend's fast f32 conv/GEMM paths (XLA CPU has no fast
          integer conv), ~10x over the int32 oracle on CPU.
        * ``"oracle"`` — the pure-jnp int32 oracle (default for bits=16,
          whose 48-bit accumulator model is already float).
        * ``"kernel"`` — the Pallas PE-array kernel (interpret mode on a
          CPU device). Availability is checked here, once, not per step.

        ``donate`` donates the int8 activation buffer to the call so XLA
        reuses it for intermediates instead of round-tripping fresh
        allocations (defaults to True off-CPU; CPU ignores donation).
        """
        if self.steps is None:
            raise ValueError(
                "plan-only program (compiled without params) cannot run")
        return self.compile_stage_runner(0, len(self.steps), route=route,
                                         donate=donate)

    def compile_stage_runner(self, start: int, stop: int, *,
                             route: str | None = None,
                             donate: bool | None = None,
                             device=None) -> "CompiledRunner":
        """Jit the contiguous step range ``[start, stop)`` as one device
        program — one *stage* of the software layer-wise pipeline
        (``repro.serving``). Activations cross stage boundaries as the same
        int8 (int16 for bits=16) tensors the full chain passes between
        steps, so chaining stage runners end to end reproduces
        :meth:`compile_runner` bit-exactly for every route (pinned by
        ``tests/test_serving.py``). ``compile_runner`` itself is the
        degenerate single-stage case ``[0, len(steps))``.

        ``device`` pins the stage to one ``jax.Device``: the stage's
        weights are put there once, and inputs are ``jax.device_put``
        onto it before dispatch, so the jit compiles and runs there. This
        is how the serving pipeline places each stage on its own device —
        the software analogue of each paper engine owning its own
        DSP/BRAM partition. Without a pin the stage uses the default
        device. The kernel route runs the Pallas kernel in interpret mode
        only on a CPU device. Placement never changes the integers: every
        route is bit-exact on any backend, so placed output == unplaced
        output (pinned by ``tests/test_serving.py``).

        The jitted function is named ``serve_<model>_<start>_<stop>``
        (the model name in identifier characters), so a profiler trace
        names each stage's device program ``jit_serve_<model>_<start>_
        <stop>``, the same in every process."""
        if self.steps is None:
            raise ValueError(
                "plan-only program (compiled without params) cannot run")
        if not (0 <= start < stop <= len(self.steps)):
            raise ValueError(
                f"stage range [{start}, {stop}) outside the "
                f"{len(self.steps)}-step chain")
        steps = tuple(self.steps[start:stop])
        skip = self.skip_across(start)
        if skip is not None:
            raise ValueError(
                f"stage [{start}, {stop}) would start across skip tensor "
                f"{skip!r}: one tensor, the previous step's output, "
                f"crosses a stage boundary")
        src = self.steps[start - 1].name if start else INPUT
        route = self._resolve_route(route, steps)
        target = device if device is not None else jax.devices()[0]
        interpret = (_kernel_interpret(target) if route == "kernel"
                     else False)
        if donate is None:
            donate = target.platform != "cpu"
        bits = self.bits
        weights = jax.device_put(
            {s.name: {"wq": s.wq, "bias_q": s.bias_q, "shift": s.shift}
             for s in steps if s.kind in COMPUTE}, device)

        def chain(xq: jnp.ndarray, weights: dict) -> jnp.ndarray:
            def mac(xq, step):
                step = dataclasses.replace(step, **weights[step.name])
                if route == "kernel":
                    return _step_kernel(xq, step, interpret)
                if route == "f32":
                    return _step_exact_f32(xq, step)
                return _step_oracle(xq, step, bits)
            return _walk(steps, xq, src, mac, bits)

        chain.__name__ = (
            f"serve_{re.sub(r'[^0-9A-Za-z_]', '_', self.model.name)}"
            f"_{start}_{stop}")
        fn = jax.jit(chain, donate_argnums=(0,) if donate else ())
        return CompiledRunner(program=self, route=route, donate=donate,
                              fn=fn, weights=weights, start=start,
                              stop=stop, device=device)


@dataclasses.dataclass
class CompiledRunner:
    """One jitted device program for a contiguous step range of the engine
    chain — the whole chain for :meth:`EngineProgram.compile_runner`
    (``start == 0``, ``stop == len(steps)``), or one pipeline stage for
    :meth:`EngineProgram.compile_stage_runner`.

    ``fn(xq, weights)`` maps an int8 (int16 for bits=16) activation batch
    ``[B, H, W, C]`` to the range's output — raw final accumulators when
    the range includes the last engine, int8 activations otherwise.
    ``weights`` holds the range's weight/bias/shift schedules, put on the
    runner's device once; passing them as arguments rather than jit
    constants keeps the compiled program free of ~100 MB of embedded
    weights. A fixed batch shape compiles exactly once (``cache_size`` is
    the recompile guard the tests pin). Host-side quantize-in and
    argmax/dequant-out live here so the executor can overlap them with
    device compute; they exist only at the matching end of the chain
    (first / last stage).
    """

    program: EngineProgram
    route: str
    donate: bool
    fn: Callable[[jnp.ndarray, dict], jnp.ndarray]
    weights: dict
    start: int = 0
    stop: int = -1          # -1 == len(program.steps) (whole chain)
    device: object = None   # jax.Device pin (None = backend default)

    def __post_init__(self):
        if self.stop < 0:
            self.stop = len(self.program.steps)

    @property
    def is_first(self) -> bool:
        return self.start == 0

    @property
    def is_last(self) -> bool:
        return self.stop == len(self.program.steps)

    def quantize(self, frames) -> np.ndarray:
        """Host-side quantize of float frames (a sequence of
        ``[H, W, C]`` or an ``[N, H, W, C]`` array) onto the program's
        frozen input format, into a fresh int8 batch:
        ``quant.quantize_frames_np``, bit-identical to
        ``quant.quantize_to_exponent``. Only the first stage consumes
        float frames."""
        if not self.is_first:
            raise ValueError(
                f"stage [{self.start}, {self.stop}) does not start the "
                f"chain; it consumes the previous stage's quantized "
                f"activations, not float frames")
        return quant.quantize_frames_np(frames, self.program.e_input,
                                        self.program.bits)

    def __call__(self, xq) -> jnp.ndarray:
        """Dispatch one quantized batch; returns the device future of the
        final accumulators (async — block or fetch to synchronize). With
        donation on, a jnp input is copied first — ``jnp.asarray`` would
        alias the caller's buffer, and donating that alias invalidates
        the caller's array (host numpy input is always staged fresh).
        A ``device`` pin commits the input there first, so jit executes
        the stage on that device. The donation guard copies only when
        the input would otherwise alias: ``device_put`` onto the
        array's *current* device can return the same buffer, but a
        cross-device transfer already yields a fresh one — copying
        there too would waste an activation copy per micro-batch on
        the placed multi-device hot path."""
        if self.donate and isinstance(xq, jax.Array) and \
                (self.device is None or xq.devices() == {self.device}):
            xq = jnp.array(xq, copy=True)
        if self.device is not None:
            xq = jax.device_put(xq, self.device)
        return self.fn(jnp.asarray(xq), self.weights)

    def dequantize(self, acc) -> np.ndarray:
        """Raw final accumulators -> float32 logits on their exact po2
        scale (host side). Only the last stage emits accumulators."""
        if not self.is_last:
            raise ValueError(
                f"stage [{self.start}, {self.stop}) does not end the "
                f"chain; it emits quantized activations, not final "
                f"accumulators")
        acc = np.asarray(acc)
        scale = self.program.out_scale()
        return acc.astype(np.float32) * scale.reshape(
            (1,) * (acc.ndim - 1) + (-1,))

    def logits(self, x) -> np.ndarray:
        """Blocking convenience: float frames -> float logits. Bit-identical
        to ``program.run`` on the same route's arithmetic."""
        return self.dequantize(self(self.quantize(np.asarray(x))))

    def classify(self, x) -> np.ndarray:
        """Blocking convenience: float frames -> int class ids."""
        out = self.logits(x)
        return np.argmax(out.reshape(out.shape[0], -1), axis=-1)

    def cache_size(self) -> int:
        """Number of distinct XLA executables behind ``fn`` (recompile
        guard: one batch shape must stay at 1)."""
        return int(self.fn._cache_size())


def kernel_available(bits: int = 8) -> tuple[bool, str]:
    """Probe the Pallas kernel route once: importable and applicable."""
    if bits > 8:
        return False, ("the Pallas PE-array kernel is int8; bits=16 runs "
                       "the jnp oracle (48-bit DSP accumulation model)")
    try:
        from repro.kernels.conv2d_int8 import ops  # noqa: F401
    except Exception as e:  # pragma: no cover - depends on install
        return False, f"Pallas conv2d_int8 kernel unavailable: {e!r}"
    return True, ""


def _kernel_interpret(device) -> bool:
    """Whether the Pallas kernel runs in interpret mode on ``device``:
    compiled on a TPU, interpreted on a CPU, refused anywhere else."""
    if device.platform == "tpu":
        return False
    if device.platform == "cpu":
        return True
    raise NotImplementedError(
        f"the Pallas int8 kernel runs on TPU (or interpreted on CPU), "
        f"not on {device.platform}")


def require_kernel(bits: int = 8) -> None:
    """Raise up front (at compile/jit time, not per step) when the kernel
    route is requested but cannot run — a CI run asking for the kernel
    must not silently green-light the oracle."""
    ok, why = kernel_available(bits)
    if not ok:
        raise NotImplementedError(why)


# ---------------------------------------------------------------------------
# Step executors
# ---------------------------------------------------------------------------


def _walk(steps, xq, src: str, mac, bits: int):
    """Run ``steps`` in order over a map of named tensors that starts as
    ``{src: xq}``, dropping each tensor after its last reader; returns the
    last step's output. ``mac(x, step)`` runs a conv or fc step; pools,
    adds and average pools are plain integer ops here on every route."""
    last = {n: i for i, s in enumerate(steps) for n in s.inputs}
    env = {src: xq}
    for i, step in enumerate(steps):
        args = [env[n] for n in step.inputs]
        for n in step.inputs:
            if last[n] == i:
                env.pop(n, None)
        if step.kind == "pool":
            env[step.name] = _pool_int(args[0], step)
        elif step.kind == "add":
            with jax.named_scope(step.name):
                env[step.name] = _add_int(*args, step, bits)
        elif step.kind == "avgpool":
            with jax.named_scope(step.name):
                env[step.name] = _avgpool_int(args[0], step)
        else:
            env[step.name] = mac(args[0], step)
    return env[steps[-1].name]


def _add_int(a: jnp.ndarray, b: jnp.ndarray, step: EngineStep,
             bits: int) -> jnp.ndarray:
    """Residual add: both operands shifted left in int32 onto the finer
    of their exponents (``step.align``), summed exactly, ReLU where one
    folds, then requantized to the add's own exponent with the conv
    epilogue's shift (floor right, saturating left) and clipped."""
    s = (jnp.left_shift(a.astype(jnp.int32), step.align[0])
         + jnp.left_shift(b.astype(jnp.int32), step.align[1]))
    if step.relu:
        s = jnp.maximum(s, 0)
    return quant.requantize_output(s, 0, step.shift, bits=bits)


def _avgpool_int(xq: jnp.ndarray, step: EngineStep) -> jnp.ndarray:
    """Average pool on the integers: the window's int32 sum (a pad counts
    as zeros) over its size ``n``, rounded half up as ``(s + n // 2) //
    n`` in floor division; the exponent passes through."""
    lyr = step.layer
    lo, hi = step.pad
    n = lyr.kernel * lyr.kernel
    s = jax.lax.reduce_window(
        xq.astype(jnp.int32), jnp.int32(0), jax.lax.add,
        (1, lyr.kernel, lyr.kernel, 1), (1, lyr.stride, lyr.stride, 1),
        ((0, 0), (lo, hi), (lo, hi), (0, 0)))
    return ((s + n // 2) // n).astype(xq.dtype)


def _pool_int(xq: jnp.ndarray, step: EngineStep) -> jnp.ndarray:
    """Max pool directly on the integer activations — max is monotone in
    the po2 format, so this is exact and the exponent passes through."""
    lyr = step.layer
    lo, hi = step.pad
    # bits=16 models accumulators in float32, so the last engine's output
    # (requantize=False) may reach a trailing pool as floats.
    init = jnp.array(-jnp.inf if jnp.issubdtype(xq.dtype, jnp.floating)
                     else jnp.iinfo(xq.dtype).min, xq.dtype)
    return jax.lax.reduce_window(
        xq, init, jax.lax.max,
        (1, lyr.kernel, lyr.kernel, 1), (1, lyr.stride, lyr.stride, 1),
        ((0, 0), (lo, hi), (lo, hi), (0, 0)))


def _step_kernel(xq: jnp.ndarray, step: EngineStep,
                 interpret: bool) -> jnp.ndarray:
    from repro.kernels.conv2d_int8.ops import conv2d_int8, fc_int8
    lyr = step.layer
    emit = not step.requantize
    if step.kind == "fc":
        return fc_int8(xq.reshape(xq.shape[0], -1), step.wq, step.shift,
                       step.bias_q, relu=step.relu, interpret=interpret,
                       emit_int32=emit)
    return conv2d_int8(xq, step.wq, step.shift, step.bias_q,
                       stride=lyr.stride, padding=(step.pad, step.pad),
                       groups=lyr.groups, relu=step.relu,
                       interpret=interpret, emit_int32=emit)


def _step_oracle(xq: jnp.ndarray, step: EngineStep, bits: int) -> jnp.ndarray:
    """Pure-jnp integer oracle with the identical fused epilogue. For
    bits<=8 the arithmetic is exact int32 (bit-identical to the Pallas
    kernel); bits=16 models the DSP48's 48-bit accumulate in float32."""
    lyr = step.layer
    exact = bits <= 8
    acc_dt = jnp.int32 if exact else jnp.float32
    if step.kind == "fc":
        acc = jnp.matmul(xq.reshape(xq.shape[0], -1).astype(acc_dt),
                         step.wq.astype(acc_dt),
                         preferred_element_type=acc_dt)
    else:
        lo, hi = step.pad
        acc = jax.lax.conv_general_dilated(
            xq.astype(acc_dt), step.wq.astype(acc_dt),
            (lyr.stride, lyr.stride), ((lo, hi), (lo, hi)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=lyr.groups,
            preferred_element_type=acc_dt)
    if exact:
        # Same fused epilogue as the kernel, from the shared oracle.
        return _epilogue_int32(acc, step)
    bias = step.bias_q.astype(acc_dt)
    acc = acc + bias.reshape((1,) * (acc.ndim - 1) + (-1,))
    if step.relu:
        acc = jnp.maximum(acc, 0)
    if not step.requantize:
        return acc
    # bits=16 only from here: floor(acc / 2^sh) — shifter truncation in float.
    sh = step.shift.reshape((1,) * (acc.ndim - 1) + (-1,))
    y = jnp.floor(acc * jnp.exp2(-sh.astype(jnp.float32)))
    qmax = 2 ** (bits - 1) - 1
    return jnp.clip(y, -qmax - 1, qmax).astype(jnp.int16)


# Max MAC terms per float32 partial sum on the exact-f32 route: every
# int8*int8 product has |p| <= 2^14, and float32 represents all integers
# up to 2^24 exactly, so chains of <= 2^24 / 2^14 = 1024 products (and any
# partial reordering XLA picks) stay bit-exact.
_F32_CHUNK_MACS = 1024


def _step_exact_f32(xq: jnp.ndarray, step: EngineStep) -> jnp.ndarray:
    """int8 conv/fc via *exact* float32 arithmetic: the reduction dim is
    chunked so no partial sum can exceed 2^24, chunk results are summed in
    int32, and the identical fused epilogue requantizes. Bit-identical to
    the int32 oracle and the Pallas kernel, but it reaches the backend's
    fast f32 conv/GEMM code paths (XLA CPU lowers integer convs to slow
    generic loops).

    The proof needs *true* IEEE float32 MACs, so every dot/conv here pins
    ``Precision.HIGHEST``: with the default precision XLA lowers f32 on
    Ampere+ GPUs to TF32 and on TPU to bf16 MXU passes, whose ~8-11-bit
    mantissas cannot hold the 15-24-bit integer partial sums. HIGHEST
    forces full-f32 arithmetic on GPU and the f32-exact multi-pass
    algorithm on TPU."""
    lyr = step.layer
    wq = step.wq
    if step.kind == "fc":
        x2 = xq.reshape(xq.shape[0], -1).astype(jnp.float32)
        wf = wq.astype(jnp.float32)
        acc = jnp.zeros((x2.shape[0], wq.shape[-1]), jnp.int32)
        for k0 in range(0, x2.shape[1], _F32_CHUNK_MACS):
            part = jnp.matmul(x2[:, k0:k0 + _F32_CHUNK_MACS],
                              wf[k0:k0 + _F32_CHUNK_MACS],
                              precision=jax.lax.Precision.HIGHEST)
            acc = acc + part.astype(jnp.int32)
    else:
        R, S, Cg, M = wq.shape
        xf = xq.astype(jnp.float32)
        wf = wq.astype(jnp.float32)
        lo, hi = step.pad
        groups = lyr.groups
        c_chunk = max(1, _F32_CHUNK_MACS // (R * S))
        acc = None
        for c0 in range(0, Cg, c_chunk):
            cc = min(c_chunk, Cg - c0)
            if groups == 1:
                xs = xf[..., c0:c0 + cc]
            else:
                xs = jnp.concatenate(
                    [xf[..., g * Cg + c0:g * Cg + c0 + cc]
                     for g in range(groups)], axis=-1)
            part = jax.lax.conv_general_dilated(
                xs, wf[:, :, c0:c0 + cc, :],
                (lyr.stride, lyr.stride), ((lo, hi), (lo, hi)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups,
                precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
            acc = part if acc is None else acc + part
    return _epilogue_int32(acc, step)


def _epilogue_int32(acc: jnp.ndarray, step: EngineStep) -> jnp.ndarray:
    """The shared fused output stage on exact int32 accumulators."""
    if step.requantize:
        from repro.kernels.conv2d_int8.ref import requantize_ref
        flat = requantize_ref(acc.reshape(-1, acc.shape[-1]), step.shift,
                              step.bias_q, step.relu)
        return flat.reshape(acc.shape)
    acc = acc + step.bias_q.reshape((1,) * (acc.ndim - 1) + (-1,))
    if step.relu:
        acc = jnp.maximum(acc, 0)
    return acc


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------


def compile_model(model: CNNModel, params: Params | None = None, *,
                  theta: int = DEFAULT_THETA, bits: int = 8,
                  calib_batch: jnp.ndarray | None = None,
                  bram_total: int | None = DEFAULT_BRAM,
                  bandwidth_bytes: float = DEFAULT_BW,
                  freq_hz: float = DEFAULT_FREQ,
                  bram_weights: bool = False,
                  objective: str = "optimal") -> EngineProgram:
    """Workload -> allocation -> execution, compiled once.

    Without ``params`` this produces a *plan-only* program (Algorithms 1/2
    only) for the simulator and benchmarks. With ``params`` (and a
    ``calib_batch`` for activation ranges) the program is fully lowered and
    runnable. ``bram_total=None`` skips Algorithm 2 (compute allocation
    only, all K=1). ``bram_weights=True`` makes Algorithm 2 charge weight
    buffers against the BRAM budget and pin hot weight sets on-chip (the
    Table I BRAM-column model; plan-only analytics, never the arithmetic).
    Calibration and quantization run on :func:`host_device`.
    """
    workloads = model.layer_workloads(weight_bits=bits)
    allocs = allocate_compute(workloads, theta, objective=objective)
    if bram_total is not None:
        allocate_buffers(allocs, bram_total=bram_total,
                         bandwidth_bytes=bandwidth_bytes, freq_hz=freq_hz,
                         act_bytes=bits // 8, weights=bram_weights)
    prog = EngineProgram(model=model, bits=bits, theta_total=theta,
                         allocs=allocs, freq_hz=freq_hz)
    if params is None:
        return prog

    if calib_batch is None:
        raise ValueError("compiling an executable program needs a "
                         "calib_batch to freeze activation formats")
    amax: dict[str, float] = {}
    host = host_device()
    with jax.default_device(host):
        params = jax.device_put(params, host)
        float_forward(params, model, jax.device_put(calib_batch, host),
                      record=amax)
        prog.e_input = quant.po2_exponent(amax["__input__"], bits)
        prog.steps = _lower(model, params, amax, prog.e_input, bits)
    return prog


def _lower(model: CNNModel, params: Params, amax: dict[str, float],
           e_input: int, bits: int) -> list[EngineStep]:
    """One step per layer, each tensor on its frozen exponent: a conv or
    fc reads its input's and writes its own calibrated one, an add writes
    its own, pools keep their input's."""
    steps: list[EngineStep] = []
    e = {INPUT: e_input}
    out = model.output.name
    for lyr, src, hw in zip(model.layers, model.sources(), model.in_hws()):
        e_act = e[src[0]]
        pad = lyr.padding(hw)
        if lyr.kind in ("pool", "avgpool"):
            steps.append(EngineStep(name=lyr.name, kind=lyr.kind, layer=lyr,
                                    pad=pad, inputs=src, e_in=e_act,
                                    e_out=e_act))
            e[lyr.name] = e_act
            continue
        e_out = quant.po2_exponent(amax[lyr.name], bits)
        e[lyr.name] = e_out
        if lyr.kind == "add":
            fine = min(e_act, e[src[1]])
            align = (e_act - fine, e[src[1]] - fine)
            if max(align) > 31 - bits:
                from repro.compiler.graph import UnsupportedOpError
                raise UnsupportedOpError(
                    lyr.name, f"operand exponents {e_act} and {e[src[1]]} "
                              f"lie more than {31 - bits} bits apart: "
                              f"aligned int{bits} operands would overflow "
                              f"the int32 sum")
            steps.append(EngineStep(
                name=lyr.name, kind="add", layer=lyr, pad=pad, inputs=src,
                shift=np.int32(np.clip(e_out - fine, -31, 31)), e_in=fine,
                e_out=e_out, relu=model.has_relu(lyr), align=align))
            continue
        w = params[lyr.name]["w"]
        b = params[lyr.name]["b"]
        e_w = np.asarray(quant.po2_scale(w, axis=-1, bits=bits), np.int64)
        is_last = lyr.name == out
        # Floor each channel's weight format so (a) its bias fits the
        # int32 accumulator and (b) the output shift stays within the
        # 31-bit shifter. Without this, a channel with numerically-dead
        # weights but a significant bias would get an absurdly fine
        # accumulator scale, saturating bias_q and silently dropping the
        # bias; flooring e_w instead rounds the dead weights to zero and
        # keeps the bias exactly representable.
        b_np = np.asarray(b, np.float64)
        nz = np.abs(b_np) > 0
        b_mag = np.full(b_np.shape, -(10 ** 9), np.int64)
        b_mag[nz] = np.ceil(np.log2(np.abs(b_np[nz])))
        e_w = np.maximum(e_w, np.maximum(b_mag - 30, e_out - 31) - e_act)
        # Quantize weights once onto the (possibly floored) formats.
        qmax = 2 ** (bits - 1) - 1
        scale = jnp.exp2(-jnp.asarray(e_w, jnp.float32)).reshape(
            (1,) * (w.ndim - 1) + (-1,))
        wq = jnp.clip(jnp.round(w * scale), -qmax - 1, qmax).astype(
            jnp.int8 if bits <= 8 else jnp.int16)
        # Bias pre-scaled onto this engine's 32-bit accumulator format
        # (value = q * 2^(e_in + e_w[m])).
        acc_e = e_act + e_w
        bias_q = np.clip(np.round(b_np / np.exp2(acc_e)),
                         np.iinfo(np.int32).min, np.iinfo(np.int32).max
                         ).astype(np.int32)
        shift = np.clip(e_out - acc_e, -31, 31).astype(np.int32)
        steps.append(EngineStep(
            name=lyr.name, kind=lyr.kind, layer=lyr, pad=pad, inputs=src,
            wq=np.asarray(wq), bias_q=bias_q, shift=shift,
            e_in=e_act, e_w=e_w, e_out=e_out,
            relu=model.has_relu(lyr), requantize=not is_last))
    return steps
