"""Streaming executor over a compiled :class:`EngineProgram`.

The paper's engines overlap three things per pipeline stage: reading the
next activation rows into one half of the line buffer, computing on the
other half, and draining finished outputs (activation-buffer double
buffering, Fig. 2). :class:`EngineExecutor` is the software analogue on a
frame stream:

* ``submit(frame)`` micro-batches incoming frames to ``batch_size``;
* a full micro-batch is copied once on the host, quantized to int8 on
  the device (``CompiledRunner.quantize``) and dispatched to the jitted
  chain — JAX dispatch is async, so the device computes batch ``k``
  while the host stages batch ``k+1`` and argmax-decodes batch ``k-1``
  (the two "buffer halves" are the bounded in-flight queue);
* ``drain()`` flushes the partial tail batch (padded to the compiled
  shape so the runner never recompiles) and collects all results.

Results are per-frame class ids (``top1``) or float logits; padding
frames are dropped on the way out.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Iterable, Sequence

import jax
import numpy as np

from repro.core.program import CompiledRunner, EngineProgram

# In-flight micro-batches. Two mirrors the paper's double-buffered
# activation memory: one batch computing on-device, one being staged
# host-side; a deeper queue only adds memory, not throughput.
DEFAULT_MAX_INFLIGHT = 2


def normalize_frames(program: EngineProgram,
                     frame: np.ndarray) -> np.ndarray:
    """Accept one ``[H, W, C]`` frame or a pre-batched ``[N, H, W, C]``
    chunk, validate it against ``program``'s input spec, and return the
    ``[N, H, W, C]`` form — the submit()-side twin of
    :func:`pad_micro_batch`, shared by both executors."""
    frame = np.asarray(frame)
    if frame.ndim == 3:
        frames = frame[None]
    elif frame.ndim == 4:
        frames = frame
    else:
        raise ValueError(f"expected [H,W,C] or [N,H,W,C], got "
                         f"{frame.shape}")
    hw = program.model.input_hw
    if frames.shape[1:] != (hw, hw, program.model.input_ch):
        raise ValueError(
            f"frame shape {frames.shape[1:]} does not match the "
            f"compiled program ({hw}, {hw}, {program.model.input_ch})")
    return frames


def pad_micro_batch(program: EngineProgram,
                    frames: Sequence[np.ndarray] | np.ndarray,
                    batch_size: int) -> Sequence[np.ndarray] | np.ndarray:
    """Validate a micro-batch (a sequence of ``[H, W, C]`` frames or a
    ``[B, H, W, C]`` array) against ``program``'s input spec and pad it
    to ``batch_size`` (the fixed compiled shape) with one shared zero
    frame, without stacking it — the one batch-shaping rule both the
    single-jit and the pipelined executor share. The quantize-in copies
    each frame once, into its float32 scratch."""
    hw = program.model.input_hw
    want = (hw, hw, program.model.input_ch)
    for f in frames:
        if np.shape(f) != want:
            raise ValueError(
                f"micro-batch frame shape {np.shape(f)} does not match "
                f"the compiled program {want}")
    if len(frames) > batch_size:
        raise ValueError(f"micro-batch of {len(frames)} exceeds the "
                         f"compiled batch size {batch_size}")
    if len(frames) < batch_size:
        zero = np.zeros(want, np.float32)
        frames = [*frames, *[zero] * (batch_size - len(frames))]
    return frames


@dataclasses.dataclass
class ServeStats:
    """Steady-state accounting for one serve run."""

    frames: int = 0
    batches: int = 0
    padded_frames: int = 0
    wall_s: float = 0.0          # active serving time (idle between
    first_batch_s: float = 0.0   # drains excluded); first dispatch is
    # charged to first_batch_s (jit compile) and excluded from fps.

    @property
    def steady_fps(self) -> float:
        """Frames/s excluding the first dispatch (compile + warmup) —
        the analogue of the pipeline's steady-state rate, which is what
        Algorithm 1's model predicts. Returns 0.0 when every frame landed
        in that first batch (stream <= one micro-batch): there is no
        steady-state window to measure, not a measured rate of zero."""
        steady_wall = self.wall_s - self.first_batch_s
        steady_frames = self.frames - min(self.frames, self._first_n)
        if steady_wall <= 0 or steady_frames <= 0:
            return 0.0
        return steady_frames / steady_wall

    _first_n: int = 0


class EngineExecutor:
    """Micro-batching serve loop over one jitted engine chain.

    >>> ex = EngineExecutor(program, batch_size=32)
    >>> for frame in frames:
    ...     ex.submit(frame)            # [H, W, C] float
    >>> ids = ex.drain()                # per-frame top-1 class ids
    >>> ex.stats.steady_fps
    """

    def __init__(self, program: EngineProgram, *, batch_size: int = 32,
                 route: str | None = None,
                 donate: bool | None = None, output: str = "top1",
                 max_inflight: int = DEFAULT_MAX_INFLIGHT,
                 on_result: Callable[[object, np.ndarray], None] | None = None):
        if output not in ("top1", "logits"):
            raise ValueError(f"unknown output {output!r}")
        self.program = program
        self.batch_size = int(batch_size)
        self.output = output
        self.on_result = on_result
        # Protocol slot only: this executor raises synchronously from
        # submit_batch / flush_inflight, so the callback is never fired.
        self.on_error: Callable[[object, BaseException], None] | None = None
        self.runner: CompiledRunner = program.compile_runner(
            route=route, donate=donate)
        self.stats = ServeStats()
        self.stats._first_n = self.batch_size
        # One lock serializes the pending micro-batch, the in-flight
        # queue, and stats, so multiple producer threads (the async
        # frontend's batcher plus direct callers) can feed one executor
        # without corrupting the tail-padding path. Re-entrant because
        # _dispatch collects under the same lock when back-pressured.
        self._lock = threading.RLock()
        self._pending: list[np.ndarray] = []
        self._inflight: collections.deque = collections.deque()
        self._max_inflight = max(1, int(max_inflight))
        self._results: list[np.ndarray] = []
        self._t0: float | None = None

    # -- intake --------------------------------------------------------------

    def submit(self, frame: np.ndarray) -> None:
        """Queue one float frame ``[H, W, C]`` (or a pre-batched
        ``[N, H, W, C]`` chunk); dispatches whenever ``batch_size``
        frames are buffered."""
        frames = normalize_frames(self.program, frame)
        with self._lock:
            for f in frames:
                self._pending.append(f)
                if len(self._pending) >= self.batch_size:
                    self._dispatch(self._pending[:self.batch_size])
                    self._pending = self._pending[self.batch_size:]

    def submit_batch(self, frames: Sequence[np.ndarray] | np.ndarray,
                     n_valid: int, tag: object = None) -> None:
        """Dispatch one pre-assembled micro-batch (a sequence of
        ``[H, W, C]`` frames or a ``[B, H, W, C]`` array) directly
        (padded with zero frames to the compiled batch size if short),
        bypassing the pending buffer — the entry point the async
        frontend's batcher uses. ``tag`` is handed to ``on_result``
        with this batch's outputs. Thread-safe; blocks when
        ``max_inflight`` batches are already on device."""
        batch = pad_micro_batch(self.program, frames, self.batch_size)
        with self._lock:
            self._dispatch(batch, n_valid=n_valid, tag=tag)

    def flush_inflight(self) -> None:
        """Collect every dispatched micro-batch (delivering their
        ``on_result`` callbacks) without flushing the pending tail."""
        with self._lock:
            while self._inflight:
                self._collect_one()

    def serve(self, frames: Iterable[np.ndarray]) -> list[np.ndarray]:
        """Convenience: submit a finite stream and drain."""
        for f in frames:
            self.submit(f)
        return self.drain()

    def reset_stats(self) -> None:
        """Zero the serve statistics (between drains, not mid-stream:
        with batches still in flight the window split would be
        meaningless)."""
        with self._lock:
            if self._inflight or self._pending:
                raise RuntimeError("reset_stats with work in flight")
            self.stats = ServeStats()
            self.stats._first_n = self.batch_size
            self._t0 = None

    def replica_counts(self) -> list | None:
        """Protocol conformance: a single jitted chain is not a replica
        fleet."""
        return None

    # -- the overlap core ----------------------------------------------------

    def _dispatch(self, frames, n_valid: int | None = None,
                  tag: object = None):
        """Quantize-in + async device dispatch of one micro-batch (a
        list of frames from the pending buffer, or a ``[B, H, W, C]``
        array), quantized on the device straight into the int8 batch.
        Blocks only when ``max_inflight`` batches are already on device
        (the double-buffer back-pressure). Caller holds the lock."""
        if self._t0 is None:
            self._t0 = time.perf_counter()
        while len(self._inflight) >= self._max_inflight:
            self._collect_one()
        n = n_valid if n_valid is not None else len(frames)
        xq = self.runner.quantize(frames)
        t0 = time.perf_counter()
        acc = self.runner(xq, owned=True)   # async: a device future
        if self.stats.batches == 0:
            # First dispatch traces + compiles the whole chain; charge it
            # separately so steady_fps reflects the pipeline, not the jit.
            jax.block_until_ready(acc)
            self.stats.first_batch_s = time.perf_counter() - t0
        self._inflight.append((acc, n, tag))
        self.stats.batches += 1
        self.stats.frames += n
        self.stats.padded_frames += len(frames) - n

    def _collect_one(self) -> None:
        """Fetch the oldest in-flight batch and argmax/dequant it on the
        host — this runs while newer batches compute on device. Tagged
        batches go to ``on_result``; untagged accumulate for drain()."""
        acc, n, tag = self._inflight.popleft()
        out = self.runner.dequantize(acc)[:n]
        if self.output == "top1":
            out = np.argmax(out.reshape(n, -1), axis=-1)
        if tag is not None and self.on_result is not None:
            self.on_result(tag, out)
        else:
            self._results.append(out)

    # -- drain ---------------------------------------------------------------

    def drain(self) -> list[np.ndarray]:
        """Flush the partial tail (padded to the compiled batch shape so
        the jitted chain never recompiles), collect everything, and
        return per-frame outputs in submission order. Thread-safe."""
        with self._lock:
            if self._pending:
                tail = self._pending
                self._pending = []
                n = len(tail)
                pad = [np.zeros_like(tail[0])] * (self.batch_size - n)
                self._dispatch(tail + pad, n_valid=n)
            while self._inflight:
                self._collect_one()
            if self._t0 is not None:
                # Accumulate only the active window; a later submit()
                # opens a fresh one, so host idle between drains never
                # counts.
                self.stats.wall_s += time.perf_counter() - self._t0
                self._t0 = None
            results = self._results
            self._results = []
        if not results:
            return []
        flat = np.concatenate(results, axis=0)
        return list(flat)
