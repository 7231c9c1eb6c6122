"""The harness finds cells, configurations, traffic and metric readers
by name, and the command refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent


def _copy(tmp_path) -> Path:
    dst = tmp_path / "checkout"
    dst.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def test_every_declared_cell_and_metric_has_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        _, _, cfg, traffic = harness.load_cell(cell["name"])
        assert cfg["name"] == cell["config"]
        assert cfg["chips"] == cell["chips"]
        assert traffic["loop"] in ("open", "closed")
        assert harness.metrics_of(bench, cell["name"], "end_to_end")
        assert harness.metrics_of(bench, cell["name"], "per_layer")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]))


@pytest.mark.parametrize("config", ["tiny", "tiny_graph"])
def test_added_files_are_found_without_a_code_edit(tmp_path, config):
    """A configuration (a chain of layers, or a graph the program builds
    through its compiler's front door), a traffic mix, a cell and a
    per-layer metric dropped into a copy run there as they are, served
    through ``Server`` and correct."""
    root = _copy(tmp_path)
    (root / "bench" / "configs" / f"{config}.json").write_text(
        (HERE / f"{config}.json").read_text())
    (root / "bench" / "traffic" / "closed-c4.json").write_text(
        json.dumps({"loop": "closed", "clients": 4}))
    (root / "bench" / "metrics" / "extra.sent.py").write_text(
        "def read(obs):\n    return len(obs.sent)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = f"{config}.closed"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": "closed-c4", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "extra.sent", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "frontend", "moves": "frames_per_s",
        "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench, cell, cfg, traffic = harness.load_cell(name, root)
    assert [m["name"] for m in harness.metrics_of(bench, name,
                                                  "per_layer")] == ["extra.sent"]
    r = harness.run_cell(bench, cell, cfg, traffic, seed=11, seconds=0.5,
                         trace=True, devices=jax.devices()[:1], t_start=0.0,
                         peaks=None, root=root)
    assert r["correct"] is True
    assert r["metrics"]["extra.sent"]["value"] == r["attempted"] > 0


def test_a_graph_the_program_refuses_fails_at_once_naming_the_node():
    """tiny_residual.json stops at the compiler's front door within
    seconds, before any input is drawn or server built, and the error
    names a node of the graph: today's program refuses it at ``pool1``,
    the first tensor with two consumers, before it reaches ``b1_add``.
    Once the program runs residual graphs, this test becomes a run that
    is ``correct``, as the graph case of the test above."""
    from repro.compiler import UnsupportedOpError
    cfg = json.loads((HERE / "tiny_residual.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    t = time.perf_counter()
    with pytest.raises(UnsupportedOpError) as err:
        harness.run_cell(bench, {"name": "any"}, cfg,
                         {"loop": "closed", "clients": 4}, seed=3,
                         seconds=0.5, trace=False,
                         devices=jax.devices()[:1], t_start=0.0, peaks=None)
    assert time.perf_counter() - t < 10
    assert err.value.node == "pool1"
    assert "'pool1'" in str(err.value)


def _run(cwd: Path, *args) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "alexnet.closed",
         "--seed", "3", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    p = _run(_copy(tmp_path))
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["no.such.cell"])
def test_unknown_cell_is_refused(workload):
    with pytest.raises(SystemExit):
        harness.load_cell(workload)


def test_every_reader_file_reads_a_run():
    """Each reader under bench/metrics returns a number, or None where
    its source is absent (no device trace, no replicas on the CPU)."""
    names = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py"))
    bench = {"end_to_end": [], "per_layer": [
        {"name": n, "unit": "x"} for n in names]}
    cfg = json.loads((HERE / "tiny.json").read_text())
    r = harness.run_cell(bench, {"name": "any"}, cfg,
                         {"loop": "open", "scenario": "poisson",
                          "rate_fps": 400},
                         seed=5, seconds=0.5, trace=True,
                         devices=jax.devices()[:1], t_start=0.0, peaks=None)
    assert r["correct"] is True
    absent_on_cpu = {"device.idle.closed", "stages_roofline", "step_mfu"}
    assert set(r["metrics"]) == set(names) - absent_on_cpu
    assert all(v["value"] >= 0 for v in r["metrics"].values())
