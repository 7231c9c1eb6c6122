"""Balanced stage partitioning for the software layer-wise pipeline.

The paper's Algorithm 1 balances hardware across layers so every engine
finishes a row group at the same rate; the serving pipeline needs the dual
decision — given the *fixed* per-engine allocation the program was
compiled with, split the step chain into K contiguous stages whose modeled
busy cycles are as equal as possible, so K worker threads each finish a
micro-batch at the same rate. The partition objective (minimize the
slowest stage) is exactly Algorithm 1's T_rowmax balance, solved with the
same contiguous min-max DP the mesh allocator uses
(:func:`repro.core.allocator._partition_min_max`).

Stage weights come from :class:`~repro.core.allocator.LayerAlloc` — the
single source of truth for modeled cycles — matched to steps by layer
name: conv engines cost ``H * t_row / K`` busy cycles per frame, FC
engines ``t_row``, pools, adds and average pools zero (they ride with
whichever compute stage the cut assigns them to, as on the FPGA where
pooling hides inside the line-buffer read-out).

A cut is legal only where one tensor, the output of the step before it,
is live across it: each stage hands the next one int8 tensor. In a chain
every cut is legal; in a residual network the cuts fall between blocks,
never across a skip connection.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax

from repro.core.allocator import LayerAlloc, _partition_min_max
from repro.core.program import EngineProgram


def stage_devices(n_stages: int,
                  devices: Sequence | None = None) -> list:
    """Round-robin device assignment for K stages: stage i runs on
    ``devices[i % len(devices)]`` (default ``jax.devices()``) — each
    balanced stage gets its own accelerator when the backend has several,
    the software form of resource-partitioned multi-accelerator serving.
    On a single-device backend every stage maps to that device, so
    placement is transparent (same arithmetic, same buffers)."""
    if n_stages < 1:
        raise ValueError(f"n_stages={n_stages} < 1")
    devs = list(jax.devices() if devices is None else devices)
    if not devs:
        raise ValueError("no devices to place stages on")
    return [devs[i % len(devs)] for i in range(n_stages)]


def step_cycles(allocs: Sequence[LayerAlloc]) -> dict[str, float]:
    """Modeled per-frame busy cycles for each engine, keyed by layer name
    (pool layers map to 0.0 — they are plumbing, not compute)."""
    out: dict[str, float] = {}
    for a in allocs:
        if a.layer.macs == 0:
            out[a.layer.name] = 0.0
        elif a.layer.kind == "fc":
            out[a.layer.name] = a.t_row
        else:
            out[a.layer.name] = a.layer.H * a.t_per_output_row
    return out


@dataclasses.dataclass(frozen=True)
class StagePartition:
    """A K-way contiguous split of an ``EngineProgram``'s step chain.

    ``boundaries`` has K+1 step indices: stage i runs steps
    ``[boundaries[i], boundaries[i+1])``. ``stage_cycles`` are the modeled
    busy cycles per frame per stage; ``bottleneck`` is their max — the
    modeled steady-state cost of one pipeline beat (the T_rowmax analogue
    at micro-batch granularity)."""

    n_stages: int
    boundaries: tuple[int, ...]
    stage_cycles: tuple[float, ...]

    @property
    def bottleneck(self) -> float:
        return max(self.stage_cycles)

    @property
    def balance(self) -> float:
        """mean/max stage cycles in (0, 1]; 1.0 == perfectly balanced.
        The pipeline's modeled speedup over one monolithic stage is
        ``n_stages * balance``."""
        if self.bottleneck <= 0:
            return 1.0
        return (sum(self.stage_cycles) / self.n_stages) / self.bottleneck

    def stage_ranges(self) -> list[tuple[int, int]]:
        return [(self.boundaries[i], self.boundaries[i + 1])
                for i in range(self.n_stages)]


def legal_cuts(program: EngineProgram) -> list[int]:
    """The step indices in ``(0, len(steps))`` where a stage may start:
    only the previous step's output is live across them."""
    return [i for i in range(1, len(program.steps))
            if program.skip_across(i) is None]


def partition_from_boundaries(program: EngineProgram,
                              boundaries: Sequence[int]) -> StagePartition:
    """Build a :class:`StagePartition` for caller-chosen ``boundaries``
    (K+1 step indices covering ``[0, len(steps))``), with the same cycle
    weighting :func:`partition_program` uses — one source of truth for
    stage_cycles/balance however the cuts were picked. A cut across a
    skip connection is refused, naming the tensor it would split."""
    if program.steps is None:
        raise ValueError("plan-only program (no lowered steps) cannot be "
                         "partitioned for serving")
    bounds = tuple(boundaries)
    n_stages = len(bounds) - 1
    if (n_stages < 1 or bounds[0] != 0 or bounds[-1] != len(program.steps)
            or any(b >= e for b, e in zip(bounds, bounds[1:]))):
        raise ValueError(
            f"boundaries {bounds} is not a contiguous cover of "
            f"[0, {len(program.steps)})")
    for b in bounds[1:-1]:
        skip = program.skip_across(b)
        if skip is not None:
            raise ValueError(
                f"a stage cut before step {b} ({program.steps[b].name!r}) "
                f"would split skip tensor {skip!r}: one tensor, the "
                f"previous step's output, crosses a stage boundary")
    cycles = step_cycles(program.allocs)
    weights = [cycles.get(s.name, 0.0) for s in program.steps]
    return StagePartition(
        n_stages=n_stages, boundaries=bounds,
        stage_cycles=tuple(sum(weights[b:e])
                           for b, e in zip(bounds, bounds[1:])))


def partition_program(program: EngineProgram,
                      n_stages: int) -> StagePartition:
    """Split ``program``'s step chain into ``n_stages`` contiguous stages
    with near-equal modeled cycles (Algorithm 1's balance objective via
    the exact contiguous min-max DP).

    Only legal cuts (:func:`legal_cuts`) are candidates: the DP runs over
    the segments between them, each weighted by its steps' cycles.

    Raises when the program is plan-only (no lowered steps) or when more
    stages than segments holding a conv/fc step are requested — a stage
    of only pool steps would spin on zero modeled work.
    """
    if program.steps is None:
        raise ValueError("plan-only program (no lowered steps) cannot be "
                         "partitioned for serving")
    cuts = legal_cuts(program)
    points = [0] + cuts + [len(program.steps)]
    n_compute = sum(
        any(s.kind in ("conv", "fc") for s in program.steps[b:e])
        for b, e in zip(points, points[1:]))
    if not 1 <= n_stages <= n_compute:
        raise ValueError(
            f"n_stages={n_stages} outside [1, {n_compute}] "
            f"(segments with compute steps between legal cuts)")
    cycles = step_cycles(program.allocs)
    weights = [cycles.get(s.name, 0.0) for s in program.steps]
    seg_bounds, _ = _partition_min_max(
        [sum(weights[b:e]) for b, e in zip(points, points[1:])], n_stages)
    # The DP may cut between a compute step and a trailing zero-weight
    # pool; both cuts cost the same, but keeping a pool with its producer
    # mirrors the FPGA (pooling reads out of the producing engine's line
    # buffer). Pull each boundary forward past any leading pools.
    bounds = [points[j] for j in seg_bounds]
    legal = set(cuts)
    for i in range(1, n_stages):
        while (bounds[i] + 1 in legal and bounds[i] < bounds[i + 1] - 1
               and program.steps[bounds[i]].kind == "pool"):
            bounds[i] += 1
    return partition_from_boundaries(program, bounds)
