"""workcount.py counts what the program's layer_workloads counts at int8,
and the configuration files describe the program's paper models."""

import json
from pathlib import Path

import pytest

from bench import workcount
from repro.core import workload as W

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(model) -> dict:
    layers = []
    for lyr in model.layers:
        d = {"name": lyr.name, "kind": lyr.kind, "in_ch": lyr.in_ch,
             "out_ch": lyr.out_ch, "kernel": lyr.kernel, "stride": lyr.stride,
             "groups": lyr.groups}
        if lyr.out_size is not None:
            d["out_size"] = lyr.out_size
        layers.append(d)
    return {"input_hw": model.input_hw, "input_ch": model.input_ch,
            "layers": layers}


@pytest.mark.parametrize("name", sorted(W.CNN_MODELS))
def test_equals_layer_workloads_at_int8(name):
    model = W.CNN_MODELS[name]()
    want = model.layer_workloads(weight_bits=8)
    got = workcount.layer_work(_cfg(model))
    assert [g.name for g in got] == [w.name for w in want]
    for g, w in zip(got, want):
        assert (g.macs, g.weight_bytes, g.act_out_bytes) == (
            w.macs, w.weight_bytes, w.act_out_bytes)
        # The one departure: a fully connected layer reads its flattened
        # input once, not hw * hw times over.
        assert g.act_in_bytes == (w.C if w.kind == "fc" else w.act_in_bytes)


@pytest.mark.parametrize("config", sorted(p.stem for p in CONFIGS.glob("*.json")))
def test_config_files_hold_the_paper_models(config):
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    m = W.CNN_MODELS[config]()
    assert (cfg["input_hw"], cfg["input_ch"]) == (m.input_hw, m.input_ch)
    assert tuple(W.ConvLayer(**lyr) for lyr in cfg["layers"]) == m.layers


def test_least_time_of_a_yolo_batch():
    cfg = _cfg(W.yolo())
    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    macs = workcount.macs_per_frame(cfg)
    assert macs == 20_285_153_280
    t = workcount.least_batch_s(cfg, 8, peaks)
    assert 2 * macs * 8 / 393e12 < t < 2e-3
