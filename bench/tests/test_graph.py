"""bench/graph.py reads both configuration forms into one node list: a
chain gets today's derived pads, a graph its declared ones; a ReLU folds
only into the sole producer it follows; malformed networks are refused."""

import json
from pathlib import Path

import pytest

from bench import graph
from bench.tests.test_workcount import _cfg
from repro.core import workload as W

HERE = Path(__file__).resolve().parent
RESIDUAL = json.loads((HERE / "tiny_residual.json").read_text())


@pytest.mark.parametrize("name", sorted(W.CNN_MODELS))
def test_chain_geometry_is_the_paper_models(name):
    """Each node of a paper model's chain has the program's output size
    and (lo, hi) padding, and a ReLU on every compute layer but the
    last."""
    model = W.CNN_MODELS[name]()
    net = graph.parse(_cfg(model))
    hw = model.input_hw
    compute = [l.name for l in model.layers if l.kind != "pool"]
    for lyr, node in zip(model.layers, net.nodes, strict=True):
        assert node.name == lyr.name
        if lyr.kind != "fc":
            assert node.shape[0] == lyr.out_hw(hw)
            assert node.pad == lyr.padding(hw)
            hw = lyr.out_hw(hw)
        assert node.relu == (lyr.kind != "pool" and lyr.name != compute[-1])
    assert net.output == compute[-1]


def test_residual_graph_shapes_pads_and_folds():
    net = graph.parse(RESIDUAL)
    by = {n.name: n for n in net.nodes}
    assert (by["conv1"].shape, by["conv1"].pad) == ((16, 16, 8), (3, 3))
    assert (by["pool1"].shape, by["pool1"].pad) == ((8, 8, 8), (1, 1))
    assert (by["b1_c2"].shape, by["b1_c2"].pad) == ((4, 4, 4), (1, 1))
    assert by["b1_proj"].shape == (4, 4, 16) and by["b1_proj"].pad == (0, 0)
    assert by["pool"].shape == (1, 1, 16) and by["pool"].kernel == 4
    # ReLUs fold into their sole producers; names that stood for them
    # now name the producer, so the identity shortcut reads b1_add.
    assert not any(n.op == "relu" for n in net.nodes)
    assert by["b1_add"].relu and by["b2_add"].relu and by["b1_c1"].relu
    assert not by["b1_c3"].relu and not by["b1_proj"].relu
    assert by["b2_add"].inputs == ("b2_c3", "b1_add")
    assert by["b2_c1"].inputs == ("b1_add",)
    assert net.output == "fc" and by["fc"].weight_shape == (16, 10)


def _spec(*nodes):
    return {"graph": {"name": "g", "input": {"hw": 8, "channels": 3},
                      "nodes": list(nodes)}}


def test_relu_on_a_tensor_with_two_consumers_stays_a_node():
    net = graph.parse(_spec(
        {"op": "conv", "name": "a", "input": "input", "out_channels": 4,
         "kernel": 3},
        {"op": "relu", "name": "r", "input": "a"},
        {"op": "add", "name": "s", "inputs": ["a", "r"]},
        {"op": "flatten", "name": "f", "input": "s"},
        {"op": "fc", "name": "out", "input": "f", "out_features": 2}))
    by = {n.name: n for n in net.nodes}
    assert not by["a"].relu and by["r"].op == "relu"


CONV = {"op": "conv", "name": "c", "input": "input", "out_channels": 4,
        "kernel": 3}
FC = {"op": "fc", "name": "out", "input": "c", "out_features": 2}


@pytest.mark.parametrize("nodes,words", [
    ([dict(CONV, padding="full"), FC], "padding"),
    ([dict(CONV, kernel=[3, 3]), FC], "kernel"),
    ([dict(CONV, dilation=2), FC], "takes no 'dilation'"),
    ([dict(CONV, groups=3), FC], "groups"),
    ([CONV, {"op": "add", "name": "s", "inputs": ["c", "input"]}, FC],
     "add of"),
    ([CONV, dict(FC, input="nowhere")], "not defined"),
    ([CONV, {"op": "softmax", "name": "s", "input": "c"}], "unknown op"),
    ([CONV, FC, {"op": "relu", "name": "r", "input": "out"}], "without a ReLU"),
    ([CONV, dict(FC, name="o1"), dict(FC, name="o2")], "2 outputs"),
    ([CONV, {"op": "maxpool", "name": "p", "input": "c", "kernel": 2}],
     "conv or fc"),
])
def test_malformed_graphs_are_refused(nodes, words):
    with pytest.raises(ValueError, match=words):
        graph.parse(_spec(*nodes))
