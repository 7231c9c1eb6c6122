"""Lower an importer :class:`~repro.compiler.graph.Graph` onto the
engine's contract.

The engine executes a list of :class:`~repro.core.workload.ConvLayer`
records in topological order, each naming its producers
(``core/program.py``): conv/fc engines apply bias, their ReLU and a
requantize to int8 in their epilogue, the output engine emits raw
accumulators, and max pools, residual adds and average pools run as
integer steps between engines. Lowering maps the explicit graph onto
those layers:

* **ReLU folding** — a ``relu`` folds into the conv, fc or add that
  feeds it when it is that producer's only consumer; every reader of
  the ReLU (a block's next conv and its shortcut alike) then reads the
  folded tensor. The ReLU may also sit *after* intervening max pools
  (``conv -> pool -> relu``) whose only consumer it is: max and ReLU
  commute (both monotone), so the fold through the pool is exact.
  A conv or fc with no ReLU lowers with ``relu=False`` and still
  requantizes (a bottleneck's last conv before its add).
* **Legalization** — stride, groups and the graph's declared (lo, hi)
  pads are carried onto the layer as they are, never re-derived.
* **Rejection** — what the engine cannot run raises a typed
  :class:`UnsupportedOpError` naming the node: a ReLU that cannot fold,
  an output that is not a conv or fc, a trailing ReLU on the output,
  and (at compile time, once exponents exist) an add whose operands lie
  more than 23 bits apart. A conv after flatten is refused at import,
  by shape inference.

The output is a ready-to-compile ``(CNNModel, params-or-None)`` pair:
params are assembled when the graph nodes carry weights (the ONNX
path), otherwise ``None`` and the caller seeds them
(:func:`repro.compiler.calibrate.quantize`).
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from repro.compiler.graph import (Graph, Node, UnsupportedOpError,
                                  _square, resolve_padding)
from repro.core.workload import CNNModel, ConvLayer

_FOLDS_INTO = ("conv", "fc", "add")
_FOLDS_THROUGH = ("maxpool", "flatten")   # both commute with ReLU exactly


def lower_graph(graph: Graph) -> tuple[CNNModel, dict | None]:
    """Normalize + legalize ``graph`` into an engine-ready
    :class:`CNNModel` (plus assembled params when the graph carries
    weights). Raises :class:`UnsupportedOpError` naming the first node
    the engine cannot take."""
    consumers = graph.consumers()
    by_name = {n.name: n for n in graph.nodes}
    alias: dict[str, str] = {}      # relu/flatten -> the tensor it stands for
    relu: set[str] = set()          # the conv/fc/add nodes a ReLU folds into
    for node in graph.nodes:
        if node.op == "flatten":      # the engine's fc reads NHWC flattened
            alias[node.name] = alias.get(node.inputs[0], node.inputs[0])
        elif node.op == "relu":
            producer = _relu_producer(node, by_name, consumers)
            if producer is None:
                raise UnsupportedOpError(
                    node.name, "ReLU must be the only consumer of the "
                               "conv, fc or add it follows (optionally "
                               "through max pools, where the fold "
                               "commutes exactly)")
            relu.add(producer.name)
            alias[node.name] = alias.get(node.inputs[0], node.inputs[0])
    layers = []
    for node in graph.nodes:
        if node.name in alias:
            continue
        inputs = tuple(alias.get(s, s) for s in node.inputs)
        in_shape = graph.shapes[node.inputs[0]]
        if node.op == "fc":
            lyr = ConvLayer(node.name, math.prod(in_shape),
                            int(node.attr("out_features")), 1, kind="fc")
        elif node.op == "add":
            lyr = ConvLayer(node.name, in_shape[-1], in_shape[-1], 1,
                            kind="add", out_size=in_shape[0])
        else:
            lyr = _lower_window(node, in_shape)
        layers.append(dataclasses.replace(lyr, inputs=inputs,
                                          relu=node.name in relu))
    out = _output(graph, by_name)
    if out.name in relu:
        raise UnsupportedOpError(
            out.name, "trailing ReLU on the output layer — the output "
                      "engine emits raw accumulators (logits); fold the "
                      "activation into the consumer instead")
    compute = [n for n in graph.nodes if n.op in ("conv", "fc")]
    model = CNNModel(graph.name, graph.input_hw, graph.input_ch,
                     tuple(layers))
    return model, _collect_params(compute)


def _output(graph: Graph, by_name: dict[str, Node]) -> Node:
    """The conv or fc whose accumulators are the network's output: the
    graph's terminal, or the producer it reaches through max pools and
    flattens."""
    cur = by_name[graph.output]
    if cur.op == "relu":
        cur = by_name.get(cur.inputs[0], cur)
    while cur.op in _FOLDS_THROUGH:
        cur = by_name.get(cur.inputs[0], cur)
    if cur.op not in ("conv", "fc"):
        raise UnsupportedOpError(
            graph.output, f"the output must be a conv or fc (through max "
                          f"pools), whose int32 accumulators are the "
                          f"logits; it is {cur.op} {cur.name!r}")
    return cur


def _lower_window(node: Node, in_shape: tuple[int, ...]) -> ConvLayer:
    """A conv, max pool or average pool with its declared pads."""
    k = _square(node.name, "kernel", node.attr("kernel"))
    stride = node.attr("stride")
    stride = _square(node.name, "stride", k if stride is None else stride)
    lo, hi, out = resolve_padding(in_shape[0], k, stride,
                                  node.attr("padding"), node.name)
    if node.op == "conv":
        return ConvLayer(node.name, in_shape[-1],
                         int(node.attr("out_channels")), k, stride=stride,
                         groups=int(node.attr("groups")), out_size=out,
                         pad=(lo, hi))
    return ConvLayer(node.name, in_shape[-1], in_shape[-1], k,
                     stride=stride,
                     kind="pool" if node.op == "maxpool" else "avgpool",
                     out_size=out, pad=(lo, hi))


def _relu_producer(node: Node, by_name: dict[str, Node],
                   consumers: dict[str, list[Node]]) -> Node | None:
    """Walk back from a ReLU through max pools (and flattens) to the
    conv, fc or add it folds into, each node on the way having one
    consumer; None where anything else is in between."""
    cur = by_name.get(node.inputs[0])
    while cur is not None and len(consumers[cur.name]) == 1:
        if cur.op in _FOLDS_INTO:
            return cur
        if cur.op not in _FOLDS_THROUGH:
            return None
        cur = by_name.get(cur.inputs[0])
    return None


def _collect_params(compute_nodes: list[Node]) -> dict | None:
    """Assemble a ``cnn.init_params``-shaped dict from node-attached
    weights. All-or-nothing: a graph with weights on only some compute
    layers is a broken export, not a half-seeded model."""
    with_w = [n for n in compute_nodes if n.attr("weight") is not None]
    if not with_w:
        return None
    if len(with_w) != len(compute_nodes):
        missing = [n.name for n in compute_nodes
                   if n.attr("weight") is None]
        raise UnsupportedOpError(
            missing[0], f"graph carries weights for "
                        f"{len(with_w)}/{len(compute_nodes)} compute "
                        f"layers (missing: {', '.join(missing)}) — "
                        f"provide all or none (none = seeded init)")
    params: dict = {}
    for n in compute_nodes:
        w = jnp.asarray(np.asarray(n.attr("weight"), np.float32))
        cout = w.shape[-1]
        b = n.attr("bias")
        b = (jnp.zeros((cout,), jnp.float32) if b is None
             else jnp.asarray(np.asarray(b, np.float32)))
        params[n.name] = {"w": w, "b": b}
    return params
