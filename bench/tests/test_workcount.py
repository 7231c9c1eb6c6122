"""workcount.py counts what the program's layer_workloads counts at int8,
and node by node on a graph; the chain configuration files describe the
program's paper models and every graph configuration parses."""

import json
from pathlib import Path

import pytest

from bench import graph, workcount
from repro.core import workload as W

HERE = Path(__file__).resolve().parent
CONFIGS = HERE.parent / "configs"


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
    """A chain is the paper model of its name; a graph, which need not
    be one, has its shapes inferred and counted without error."""
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    if "graph" in cfg:
        assert cfg["name"] == config
        assert graph.parse(cfg).compute() and workcount.macs_per_frame(cfg)
        return
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


def test_residual_graph_counts_node_by_node():
    """tiny_residual.json by hand: a max pool, an add and an average pool
    have no MACs and move their activations once a frame, an add reading
    both its inputs; a flatten and the folded ReLUs are not counted. The
    least time sums the compute nodes alone."""
    cfg = json.loads((HERE / "tiny_residual.json").read_text())
    work = {w.name: w for w in workcount.layer_work(cfg)}
    assert list(work) == [n.name for n in graph.parse(cfg).nodes
                          if n.op != "flatten"]
    row = lambda n: (work[n].macs, work[n].weight_bytes,  # noqa: E731
                     work[n].act_in_bytes, work[n].act_out_bytes)
    assert row("conv1") == (16 * 16 * 7 * 7 * 3 * 8, 7 * 7 * 3 * 8,
                            32 * 32 * 3, 16 * 16 * 8)
    assert row("pool1") == (0, 0, 16 * 16 * 8, 8 * 8 * 8)
    assert row("b1_c2") == (4 * 4 * 3 * 3 * 4 * 4, 3 * 3 * 4 * 4,
                            8 * 8 * 4, 4 * 4 * 4)
    assert row("b1_add") == (0, 0, 2 * 4 * 4 * 16, 4 * 4 * 16)
    assert row("b2_add") == (0, 0, 2 * 4 * 4 * 16, 4 * 4 * 16)
    assert row("pool") == (0, 0, 4 * 4 * 16, 16)
    assert row("fc") == (16 * 10, 16 * 10, 16, 10)
    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    want = sum(max(2 * w.macs * 8 / 393e12,
                   (w.weight_bytes + 8 * (w.act_in_bytes + w.act_out_bytes))
                   / 819e9) for w in work.values() if w.kind in graph.COMPUTE)
    assert workcount.least_batch_s(cfg, 8, peaks) == pytest.approx(want)
