"""A whole run on the CPU at a small size, the chip check skipped: a
sound program is correct, and each fault planted in the served path
turns ``correct`` false."""

import json
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import check, harness
from bench.reference import cnn as reference
from repro.core.program import CompiledRunner
from repro.serving.frontend import AsyncFrontend

HERE = Path(__file__).resolve().parent
TINY = json.loads((HERE / "tiny.json").read_text())
TINY_GRAPH = json.loads((HERE / "tiny_graph.json").read_text())
CLOSED = {"loop": "closed", "clients": 8}
OPEN = {"loop": "open", "scenario": "poisson", "rate_fps": 400}


def _run(traffic=CLOSED, trace=False, cell="alexnet.closed", cfg=TINY):
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return harness.run_cell(bench, {"name": cell}, cfg, traffic, seed=2**31 + 3,
                            seconds=1.0, trace=trace, devices=jax.devices()[:1],
                            t_start=0.0, peaks=None)


@pytest.mark.parametrize("traffic,cell,cfg", [
    (CLOSED, "alexnet.closed", TINY), (OPEN, "alexnet.closed", TINY),
    (CLOSED, "alexnet.closed", TINY_GRAPH)], ids=["closed", "open", "graph"])
def test_sound_run_is_correct(traffic, cell, cfg):
    r = _run(traffic, cell=cell, cfg=cfg)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
    assert r["checks"]["max_gap_lsb"]["value"] == 0
    assert set(r["metrics"]) >= {"setup_s"}
    assert r["device"]["platform"] == "cpu"


def _patch(monkeypatch, name, wrap):
    orig = getattr(CompiledRunner, name)
    monkeypatch.setattr(CompiledRunner, name,
                        lambda self, x: wrap(self, orig(self, x)))


@pytest.mark.parametrize("cfg", [TINY, TINY_GRAPH], ids=["chain", "graph"])
def test_answer_altered_where_produced(monkeypatch, cfg):
    def one_lsb_up(runner, out):
        out = np.array(out)
        out.reshape(len(out), -1)[0, 0] += runner.program.out_scale()[0]
        return out
    _patch(monkeypatch, "dequantize", one_lsb_up)
    r = _run(cfg=cfg)
    assert r["correct"] is False
    assert r["checks"]["max_gap_lsb"]["value"] == 1


@pytest.mark.parametrize("cfg", [TINY, TINY_GRAPH], ids=["chain", "graph"])
def test_int4_control_in_the_programs_place(monkeypatch, cfg):
    """Every served answer replaced, before the check, by the control's:
    the reference with its weights held at int4 on the same frame."""
    orig = check.served_logits

    def control_served(cfg, params, calib, pool, sent, **kw):
        low = reference.build(cfg, params, calib, weight_bits=4)
        got = reference.logits(low, pool)
        for s in sent:
            if s.answered:
                s.value = got[s.frame]
        return orig(cfg, params, calib, pool, sent, **kw)
    monkeypatch.setattr(check, "served_logits", control_served)
    r = _run(cfg=cfg)
    assert r["correct"] is False
    assert r["checks"]["max_gap_lsb"]["value"] > 3


def test_answers_handed_to_the_wrong_requests(monkeypatch):
    _patch(monkeypatch, "dequantize", lambda runner, out: np.roll(out, 1, 0))
    assert _run()["correct"] is False


def test_half_of_the_batch_left_out(monkeypatch):
    def drop_half(runner, xq):
        xq = np.array(xq)
        xq[len(xq) // 2:] = 0
        return xq
    _patch(monkeypatch, "quantize", drop_half)
    assert _run()["correct"] is False


def test_answers_that_never_come(monkeypatch):
    """Once the window opens, every third batch's answers come only after
    the run has stopped waiting for them."""
    monkeypatch.setattr(harness, "WAIT_AFTER_S", 0.5)
    armed, calls, timers = [], [], []
    orig_warm, orig_result = harness.warm_up, AsyncFrontend._on_result

    def warm_up(*a, **k):
        orig_warm(*a, **k)
        armed.append(True)

    def on_result(self, tag, outputs):
        calls.append(1)
        if armed and len(calls) % 3 == 0:
            late = threading.Timer(3.0, orig_result, (self, tag, outputs))
            late.start()
            timers.append(late)
            return
        orig_result(self, tag, outputs)
    monkeypatch.setattr(harness, "warm_up", warm_up)
    monkeypatch.setattr(AsyncFrontend, "_on_result", on_result)
    r = _run()
    for late in timers:
        late.join(timeout=10)
        assert not late.is_alive()
    assert r["correct"] is False
    assert r["checks"]["unanswered"]["value"] > 0
