"""Decide ``correct``: every answer the window was due to give, against
the plain reference.

The served path returns each frame's logits: the output node's int32
accumulators on their exact power-of-two scale. The reference computes
the same integers, so the comparison is exact. Two numbers are compared,
each with its limit:

* ``max_gap_lsb``: over every request answered, the largest gap between
  a served logit and the reference's logit for the same pool frame, in
  units of one accumulator step of that channel. Limit 0: one wrong
  integer anywhere, or a result handed to another request, fails.
* ``unanswered``: requests of the window with no answer a minute after
  it closed, or that failed. Limit 0.

The readings these limits were set from are in PERF.md.
"""

from __future__ import annotations

import numpy as np

from bench.reference import cnn as reference

LIMITS = {"max_gap_lsb": 0, "unanswered": 0}


def gap_lsb(served: np.ndarray, want: np.ndarray,
            scale: np.ndarray) -> float:
    """Largest |served - want| in units of ``scale`` (per channel)."""
    if len(served) == 0:
        return float("inf")
    d = np.abs(served.astype(np.float64) - want.astype(np.float64))
    return float(np.max(d / scale.astype(np.float64)))


def served_logits(cfg: dict, params: dict, calib: np.ndarray,
                  pool: np.ndarray, sent: list, *, device=None) -> dict:
    """The compared numbers of one run, each beside its limit."""
    net = reference.build(cfg, params, calib)
    want = reference.logits(net, pool, block=cfg["server"]["batch"],
                            device=device)
    got, idx = [], []
    for s in sent:
        if s.answered:
            got.append(np.asarray(s.value).reshape(-1))
            idx.append(s.frame)
    unanswered = len(sent) - len(got)
    served = np.stack(got) if got else np.zeros((0, want.shape[-1]))
    gap = gap_lsb(served, want.reshape(len(want), -1)[idx] if got
                  else served, net.out_scale)
    return {"max_gap_lsb": {"value": gap, "limit": LIMITS["max_gap_lsb"]},
            "unanswered": {"value": unanswered,
                           "limit": LIMITS["unanswered"]}}


def passed(checks: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())
