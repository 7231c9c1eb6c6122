"""Operations and bytes a frame needs, node by node, at int8 widths.

For a chain, a copy of the CNN half of
``CNNModel.layer_workloads(weight_bits=8)`` in ``repro.core.workload``
(the paper's per-layer MACs, weight volume and activation sizes), kept
here so that no change to the program can move the yardstick; for a
graph, the same count over its nodes (``bench/graph.py``). It counts the
work the model defines, whichever route computes it: padding, im2col
copies and extra passes are not work. One departure: a fully connected
layer reads its flattened input once, ``in_ch`` bytes a frame, not
``hw * hw * in_ch`` as the original counts.

Max pooling, average pooling, an add and a lone ReLU have no MACs and
no weights: they read their inputs (an add each of its two) and write
their output once a frame. A folded ReLU is part of its producer and a
flatten is a view: neither is counted.
"""

from __future__ import annotations

import dataclasses
import math

from bench import graph

BYTES = 1  # int8 weights and activations


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    kind: str
    macs: int           # multiply-accumulates per frame
    weight_bytes: int   # resident weights
    act_in_bytes: int   # activation bytes read per frame
    act_out_bytes: int  # activation bytes written per frame


def layer_work(cfg: dict) -> list[LayerWork]:
    out = []
    for n in graph.parse(cfg).nodes:
        act_in = math.prod(n.in_shape) * BYTES
        act_out = math.prod(n.shape) * BYTES
        if n.op in graph.COMPUTE:
            w = math.prod(n.weight_shape)
            macs = w * math.prod(n.shape[:-1])   # weights x output pixels
            out.append(LayerWork(n.name, n.op, macs, w * BYTES, act_in,
                                 act_out))
        elif n.op != "flatten":
            reads = 2 * act_in if n.op == "add" else act_in
            out.append(LayerWork(n.name, n.op, 0, 0, reads, act_out))
    return out


def macs_per_frame(cfg: dict) -> int:
    return sum(w.macs for w in layer_work(cfg))


def least_batch_s(cfg: dict, batch: int, peaks: dict) -> float:
    """The least time one chip needs for a batch: over compute nodes
    (conv and fc), the larger of its int8 operations at the int8 peak and
    its bytes (weights once, activations per frame) at the HBM bandwidth.
    Nodes of bandwidth alone (max pool, add, average pool, a lone ReLU)
    are left out in every configuration alike: a route may fuse them into
    their producer's pass, so their bytes are no time that every route
    has to spend, and without them the least time stays a lower bound."""
    t = 0.0
    for w in layer_work(cfg):
        if w.macs == 0:
            continue
        ops = 2 * w.macs * batch
        moved = w.weight_bytes + batch * (w.act_in_bytes + w.act_out_bytes)
        t += max(ops / peaks["int8_ops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return t
