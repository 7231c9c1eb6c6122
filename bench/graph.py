"""A configuration's network as nodes with their shapes, worked out in
the benchmark's own code. The reference, the draws and the work count
read it; it imports nothing of the program.

A configuration states its network in one of two forms:

* ``"layers"``, beside ``input_hw`` and ``input_ch``: a chain of
  ``conv``, ``pool`` (max) and ``fc`` layers. A layer's output size is
  ``out_size`` where given, else its input over the stride (1 for fc);
  its padding is what that output needs, split with the odd pixel at the
  end. Every compute layer but the last has a ReLU.
* ``"graph"``: the compiler's JSON graph spec, ``name``, ``input {hw,
  channels}`` and ``nodes``, each with ``op``, ``name``, ``input``
  (``inputs``, two of them, for ``add``) and the op's attributes:
  ``conv`` (``out_channels``, ``kernel``, ``stride`` 1, ``padding``
  "same", ``groups`` 1, ``in_channels``), ``fc`` (``out_features``,
  ``in_features``), ``maxpool`` and ``avgpool`` (``kernel``, ``stride``
  the kernel, ``padding`` "valid"), ``relu``, ``flatten`` and ``add``.
  A padding is a symmetric int, "valid" (none) or "same" (output
  ceil(in / stride), the odd pixel at the end). Kernels and strides are
  square: one int.

Both come out as one list of :class:`Node` in topological order. A
``relu`` that is the only consumer of a conv, fc or add folds into it
(``relu=True``), and its name then stands for that node's output. A
fully connected node reads its input flattened in NHWC order. The
network's output is its one unconsumed node: a conv or fc with no ReLU.
"""

from __future__ import annotations

import dataclasses
import math

INPUT = "input"                 # the name of the network's input tensor
COMPUTE = ("conv", "fc")

# op -> (required attributes, optional attributes with their defaults)
_ATTRS = {
    "conv": (("out_channels", "kernel"),
             {"stride": 1, "padding": "same", "groups": 1,
              "in_channels": None}),
    "fc": (("out_features",), {"in_features": None}),
    "maxpool": (("kernel",), {"stride": None, "padding": "valid"}),
    "avgpool": (("kernel",), {"stride": None, "padding": "valid"}),
    "relu": ((), {}),
    "flatten": ((), {}),
    "add": ((), {}),
}


@dataclasses.dataclass(frozen=True)
class Node:
    op: str
    name: str
    inputs: tuple[str, ...]
    in_shape: tuple[int, ...]       # first input: (hw, hw, c) or (n,)
    shape: tuple[int, ...]          # output
    kernel: int = 1
    stride: int = 1
    pad: tuple[int, int] = (0, 0)   # (lo, hi), the same on both axes
    groups: int = 1
    relu: bool = False              # a folded ReLU (conv, fc, add)

    @property
    def weight_shape(self) -> tuple[int, ...]:
        """HWIO for a conv, (in, out) for a fully connected node."""
        if self.op == "fc":
            return (math.prod(self.in_shape), self.shape[0])
        k = self.kernel
        return (k, k, self.in_shape[-1] // self.groups, self.shape[-1])


@dataclasses.dataclass(frozen=True)
class Net:
    name: str
    input_hw: int
    input_ch: int
    nodes: tuple[Node, ...]
    output: str

    @property
    def frame(self) -> tuple[int, int, int]:
        return (self.input_hw, self.input_hw, self.input_ch)

    def compute(self) -> list[Node]:
        return [n for n in self.nodes if n.op in COMPUTE]


def parse(cfg: dict) -> Net:
    """The configuration's network, in either form."""
    if "graph" in cfg:
        return _graph(cfg["graph"])
    return _chain(cfg)


def _chain(cfg: dict) -> Net:
    layers = cfg["layers"]
    last = max(i for i, lyr in enumerate(layers) if lyr["kind"] != "pool")
    hw = cfg["input_hw"]
    shape, prev = (hw, hw, cfg["input_ch"]), INPUT
    nodes = []
    for i, lyr in enumerate(layers):
        name, kind, stride = lyr["name"], lyr["kind"], lyr.get("stride", 1)
        if kind == "fc":
            if math.prod(shape) != lyr["in_ch"]:
                raise ValueError(f"layer {name!r}: in_ch {lyr['in_ch']} but "
                                 f"its input holds {math.prod(shape)}")
            node = Node("fc", name, (prev,), shape, (lyr["out_ch"],),
                        relu=i != last)
        else:
            if len(shape) != 3 or shape[-1] != lyr["in_ch"]:
                raise ValueError(f"layer {name!r}: in_ch {lyr['in_ch']} but "
                                 f"its input is {shape}")
            k = lyr["kernel"]
            o = lyr.get("out_size") or hw // stride
            need = max((o - 1) * stride + k - hw, 0)
            node = Node("maxpool" if kind == "pool" else "conv", name,
                        (prev,), shape, (o, o, lyr["out_ch"]), kernel=k,
                        stride=stride, pad=(need // 2, need - need // 2),
                        groups=lyr.get("groups", 1),
                        relu=kind == "conv" and i != last)
            hw = o
        nodes.append(node)
        shape, prev = node.shape, name
    return _finish(cfg.get("name", "chain"), cfg["input_hw"],
                   cfg["input_ch"], nodes)


def _square(name: str, what: str, v) -> int:
    if isinstance(v, bool) or not isinstance(v, int) or v < 1:
        raise ValueError(f"node {name!r}: {what} must be a positive int, "
                         f"got {v!r}")
    return v


def _padding(name: str, hw: int, k: int, stride: int,
             padding) -> tuple[int, int, int]:
    """-> (lo, hi, out) for one spatial axis."""
    if padding == "same":
        out = -(-hw // stride)
        need = max((out - 1) * stride + k - hw, 0)
        return need // 2, need - need // 2, out
    if padding == "valid":
        padding = 0
    if isinstance(padding, bool) or not isinstance(padding, int) \
            or padding < 0:
        raise ValueError(f"node {name!r}: padding must be 'same', 'valid' "
                         f"or an int >= 0, got {padding!r}")
    out = (hw + 2 * padding - k) // stride + 1
    if out < 1:
        raise ValueError(f"node {name!r}: no output on input {hw}")
    return padding, padding, out


def _graph(spec: dict) -> Net:
    hw, ch = int(spec["input"]["hw"]), int(spec["input"]["channels"])
    shapes = {INPUT: (hw, hw, ch)}
    nodes = []
    for entry in spec["nodes"]:
        op, name = entry["op"], entry["name"]
        if op not in _ATTRS:
            raise ValueError(f"node {name!r}: unknown op {op!r}")
        if name in shapes:
            raise ValueError(f"node {name!r}: name used twice")
        required, optional = _ATTRS[op]
        a = dict(optional)
        for key, val in entry.items():
            if key in ("op", "name", "input", "inputs"):
                continue
            if key not in required and key not in optional:
                raise ValueError(f"node {name!r}: {op} takes no {key!r}")
            a[key] = val
        for key in required:
            if key not in a:
                raise ValueError(f"node {name!r}: {op} needs {key!r}")
        inputs = tuple(entry["inputs"]) if op == "add" else (entry["input"],)
        if len(inputs) != (2 if op == "add" else 1):
            raise ValueError(f"node {name!r}: {op} takes 2 inputs")
        for src in inputs:
            if src not in shapes:
                raise ValueError(f"node {name!r}: input {src!r} is not "
                                 f"defined before it")
        nodes.append(_infer(op, name, inputs, shapes, a))
        shapes[name] = nodes[-1].shape
    return _finish(str(spec["name"]), hw, ch, nodes)


def _infer(op: str, name: str, inputs: tuple, shapes: dict, a: dict) -> Node:
    src = shapes[inputs[0]]
    if op in ("conv", "maxpool", "avgpool"):
        if len(src) != 3:
            raise ValueError(f"node {name!r}: {op} needs a spatial input, "
                             f"got {src}")
        k = _square(name, "kernel", a["kernel"])
        stride = _square(name, "stride",
                         k if a["stride"] is None else a["stride"])
        lo, hi, out = _padding(name, src[0], k, stride, a["padding"])
        cout, groups = src[2], 1
        if op == "conv":
            cout, groups = int(a["out_channels"]), int(a["groups"])
            if a["in_channels"] not in (None, src[2]):
                raise ValueError(f"node {name!r}: in_channels "
                                 f"{a['in_channels']}, input has {src[2]}")
            if groups < 1 or src[2] % groups or cout % groups:
                raise ValueError(f"node {name!r}: groups {groups} divide "
                                 f"neither {src[2]} nor {cout}")
        return Node(op, name, inputs, src, (out, out, cout), kernel=k,
                    stride=stride, pad=(lo, hi), groups=groups)
    if op == "fc":
        if a["in_features"] not in (None, math.prod(src)):
            raise ValueError(f"node {name!r}: in_features "
                             f"{a['in_features']}, input holds "
                             f"{math.prod(src)}")
        return Node(op, name, inputs, src, (int(a["out_features"]),))
    if op == "flatten":
        return Node(op, name, inputs, src, (math.prod(src),))
    if op == "add" and shapes[inputs[1]] != src:
        raise ValueError(f"node {name!r}: add of {src} and "
                         f"{shapes[inputs[1]]}")
    return Node(op, name, inputs, src, src)


def _finish(name: str, hw: int, ch: int, nodes: list[Node]) -> Net:
    """Fold each ReLU that is the only consumer of a conv, fc or add into
    it, and find the output."""
    consumers: dict[str, list[str]] = {}
    for n in nodes:
        for src in n.inputs:
            consumers.setdefault(src, []).append(n.name)
    by_name = {n.name: n for n in nodes}
    alias: dict[str, str] = {}
    for n in nodes:
        p = by_name.get(n.inputs[0])
        if (n.op == "relu" and p is not None and p.op in COMPUTE + ("add",)
                and consumers[p.name] == [n.name] and not p.relu):
            by_name[p.name] = dataclasses.replace(p, relu=True)
            alias[n.name] = p.name
    folded = [dataclasses.replace(by_name[n.name], inputs=tuple(
        alias.get(s, s) for s in n.inputs)) for n in nodes
        if n.name not in alias]
    used = {s for n in folded for s in n.inputs}
    ends = [n for n in folded if n.name not in used]
    if len(ends) != 1:
        raise ValueError(f"network {name!r} has {len(ends)} outputs: "
                         f"{[n.name for n in ends]}")
    out = ends[0]
    if out.op not in COMPUTE or out.relu:
        raise ValueError(f"network {name!r}: the output {out.name!r} has to "
                         f"be a conv or fc without a ReLU")
    return Net(name, hw, ch, tuple(folded), out.name)
