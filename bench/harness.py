"""One run of one cell: set up the server, drive the window, read the
metrics, check the served logits against the reference.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json`` and each metric's reader
in ``bench/metrics/<metric>.py`` (a ``read(obs)`` that returns a number,
or None where it finds nothing to read). A configuration states its
network as a chain of ``"layers"`` or as a ``"graph"`` of nodes
(``bench/graph.py``); the program gets a graph through its compiler's
front door.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import check, drive, generator, inputs, trace as trace_mod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WAIT_AFTER_S = 60.0          # how long answers due in the window may take
WARM_BATCHES = 4             # open loop: full batches sent before the window
WARM_ROUNDS = 3              # closed loop: clients x rounds before the window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """-> (benchmark, cell, configuration, traffic) for cell ``name``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (known: "
                         f"{', '.join(cells)})")
    cell = cells[name]
    cfg = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, traffic


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind (``end_to_end`` / ``per_layer``)."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Observation:
    """What one run saw; the metric readers take their numbers from it."""

    cfg: dict
    chips: int
    batch: int
    setup_s: float
    t0: float                       # window start, host clock
    t1: float                       # window end
    sent: list                      # drive.Sent of the window
    busy_before: list               # stage busy seconds, per replica
    busy_after: list
    peaks: dict | None              # bench/peaks.json row of the device
    trace: dict | None = None       # trace.reduce() of the window

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def completed_in_window(self) -> int:
        return sum(1 for s in self.sent
                   if s.answered and self.t0 <= s.t_done <= self.t1)


class CompileCounter:
    """Counts backend compiles while ``on`` (one listener a process)."""

    _instance = None

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


def _stage_busy(executor) -> list:
    reps = getattr(executor, "replicas", None) or [executor]
    return [list(r.stage_busy_s) for r in reps]


def build_model(cfg: dict):
    """The configuration's model as the program takes it: a chain of
    layers as ``CNNModel``, a graph through the compiler's front door
    (``repro.compiler.import_source``), which raises
    ``UnsupportedOpError`` naming the first node the program cannot
    run."""
    if "graph" in cfg:
        from repro.compiler import import_source
        model, _ = import_source(cfg["graph"])
        return model
    from repro.core.workload import CNNModel, ConvLayer
    return CNNModel(cfg["name"], cfg["input_hw"], cfg["input_ch"],
                    tuple(ConvLayer(**lyr) for lyr in cfg["layers"]))


@dataclasses.dataclass
class Started:
    """A server built from a configuration and a seed, and its inputs."""

    server: object
    name: str
    batch: int
    params: dict
    calib: np.ndarray
    pool: np.ndarray
    split: dict

    @property
    def executor(self):
        return self.server.runtime(self.name).executor


def start(cfg: dict, seed: int, device) -> Started:
    """Draw the inputs, compile the configuration through the program's
    compiler and bring the server up with ``build_server``."""
    from repro.compiler import quantize
    from repro.serving import ServerConfig, build_server
    from repro.serving.server import ProgramRegistry

    split = {}
    t = time.perf_counter()
    model = build_model(cfg)          # a graph the program refuses stops here
    host_s = time.perf_counter() - t

    t = time.perf_counter()
    params, calib, pool = inputs.make_inputs(cfg, seed, device)
    split["inputs_s"] = time.perf_counter() - t

    t = time.perf_counter()
    registry = ProgramRegistry()
    registry.register(cfg["name"], quantize(model, params, bits=cfg["bits"],
                                            seed=seed, calib=calib))
    split["host_compile_s"] = host_s + time.perf_counter() - t

    srv = cfg["server"]
    t = time.perf_counter()
    server = build_server(registry, ServerConfig(
        stages=srv["stages"], batch=srv["batch"], output=srv["output"],
        seed=seed, replicas=srv["replicas"],
        replica_mode=srv["replica_mode"]))
    split["build_server_s"] = time.perf_counter() - t
    return Started(server=server, name=cfg["name"], batch=int(srv["batch"]),
                   params=params, calib=calib, pool=pool, split=split)


def warm_up(st: Started, plan) -> None:
    """Serve a few rounds of full batches through ``Server.submit``
    before the window, so the frontend exists and every shape is hot."""
    warm = generator.Plan(loop="closed", frame_idx=plan.frame_idx,
                          clients=plan.clients or WARM_BATCHES * st.batch)
    drive.wait_all(drive.closed_loop(
        st.server, st.name, st.pool, warm, t_end=time.perf_counter() + 600,
        start=len(plan.frame_idx) // 2, limit=WARM_ROUNDS * warm.clients),
        time.perf_counter() + 600)


def run_cell(bench: dict, cell: dict, cfg: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool, devices: list,
             t_start: float, peaks: dict | None, root: Path = ROOT) -> dict:
    """One whole run; returns the result line as a dict. Metric readers
    are looked up under ``root``."""
    import jax
    from jax.profiler import TraceAnnotation

    st = start(cfg, seed, devices[0])
    server, name, executor, pool = st.server, st.name, st.executor, st.pool
    params, calib, split, batch = st.params, st.calib, st.split, st.batch
    try:
        t = time.perf_counter()
        plan = generator.make_plan(traffic, seed=seed, seconds=seconds,
                                   pool=len(pool))
        warm_up(st, plan)
        split["warmup_s"] = time.perf_counter() - t

        compiles = CompileCounter.get()
        log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        busy_before = _stage_busy(executor)
        compiles.on = True
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with TraceAnnotation(trace_mod.WINDOW_SPAN):
            if plan.loop == "open":
                sent = drive.open_loop(server, name, pool, plan, t0)
                time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
            else:
                sent = drive.closed_loop(server, name, pool, plan,
                                         t_end=t0 + seconds)
            t1 = time.perf_counter()
        busy_after = _stage_busy(executor)
        compiles.on = False
        if trace:
            jax.profiler.stop_trace()
        drive.wait_all(sent, t1 + WAIT_AFTER_S)
        memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    finally:
        try:
            server.close()
        except Exception as e:  # noqa: BLE001 - the check counts the loss
            log(f"server.close: {e!r}")
    del server, executor, st
    gc.collect()

    obs = Observation(cfg=cfg, chips=len(devices), batch=batch,
                      setup_s=setup_s, t0=t0, t1=t1, sent=sent,
                      busy_before=busy_before, busy_after=busy_after,
                      peaks=peaks)
    if trace:
        try:
            recorded = trace_mod.load(log_dir)
            chips = [d.id for d in devices]
            if not set(chips) <= set(recorded.chips):
                chips = sorted(recorded.chips)[:len(devices)]
            obs.trace = trace_mod.reduce(recorded, chips)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    log("setup: " + ", ".join(f"{k} {v:.3f}" for k, v in split.items())
        + f"; setup_s {setup_s:.3f}")
    log(f"traffic: {plan.record}; compiles inside the window: "
        f"{compiles.count}")
    if plan.loop == "open":
        log(f"generator: {generator.pacing_report(*_pacing(sent))}")
    log(f"window: {len(sent)} requests in {obs.window_s:.3f}s, "
        f"{obs.completed_in_window()} completed inside it")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, cell["name"], kind):
        value = reader(m["name"], root)(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    other = "end_to_end" if trace else "per_layer"
    log("other readings: " + json.dumps({
        m["name"]: reader(m["name"], root)(obs)
        for m in metrics_of(bench, cell["name"], other)}))

    t = time.perf_counter()
    checks = check.served_logits(cfg, params, calib, pool, sent,
                                 device=devices[0])
    log(f"reference check: {time.perf_counter() - t:.3f}s")
    failed = sum(1 for s in sent if not s.answered)
    dev = devices[0]
    result = {
        "correct": check.passed(checks),
        "attempted": len(sent),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": memory},
    }
    if trace and obs.trace is not None:
        result["device"]["busy_s"] = obs.trace["busy_s"]
        result["device"]["window_s"] = obs.trace["window_s"]
        result["breakdown"] = {"device_ops": obs.trace["device_ops"],
                               "idle_gaps": obs.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def log_checks(result: dict) -> None:
    """Each compared number beside its limit, on standard error."""
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")


def _pacing(sent) -> tuple[np.ndarray, np.ndarray]:
    return (np.asarray([s.due for s in sent]),
            np.asarray([s.t_submit for s in sent]))
