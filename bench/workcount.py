"""Operations and bytes a frame needs, layer by layer, at int8 widths.

A copy of the CNN half of ``CNNModel.layer_workloads(weight_bits=8)``
in ``repro.core.workload`` (the paper's per-layer MACs, weight volume
and activation sizes), kept here so that no change to the program can
move the yardstick. It counts the work the model defines, whichever
route computes it: padding, im2col copies and extra passes are not work.
One departure: a fully connected layer reads ``in_ch`` bytes a frame,
not ``hw * hw * in_ch`` as the original counts.
"""

from __future__ import annotations

import dataclasses

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
    out, hw = [], cfg["input_hw"]
    for lyr in cfg["layers"]:
        kind, cin, cout = lyr["kind"], lyr["in_ch"], lyr["out_ch"]
        stride = lyr.get("stride", 1)
        if kind == "fc":
            o = 1
        else:
            o = lyr.get("out_size") or hw // stride
        if kind == "pool":
            out.append(LayerWork(lyr["name"], kind, 0, 0,
                                 hw * hw * cin * BYTES, o * o * cout * BYTES))
        elif kind == "fc":
            # in_ch is already the flattened input: the program's copy
            # multiplies it by hw * hw once more.
            out.append(LayerWork(lyr["name"], kind, cin * cout,
                                 cin * cout * BYTES, cin * BYTES, cout * BYTES))
        else:
            k, cin_g = lyr["kernel"], cin // lyr.get("groups", 1)
            out.append(LayerWork(lyr["name"], kind, o * o * k * k * cin_g * cout,
                                 k * k * cin_g * cout * BYTES,
                                 hw * hw * cin * BYTES, o * o * cout * BYTES))
        hw = o
    return out


def macs_per_frame(cfg: dict) -> int:
    return sum(w.macs for w in layer_work(cfg))


def least_batch_s(cfg: dict, batch: int, peaks: dict) -> float:
    """The least time one chip needs for a batch: over compute layers,
    the larger of its int8 operations at the int8 peak and its bytes
    (weights once, activations per frame) at the HBM bandwidth."""
    t = 0.0
    for w in layer_work(cfg):
        if w.macs == 0:
            continue
        ops = 2 * w.macs * batch
        moved = w.weight_bytes + batch * (w.act_in_bytes + w.act_out_bytes)
        t += max(ops / peaks["int8_ops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return t
