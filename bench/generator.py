"""The one traffic generator: a traffic file's parameters plus a seed
give the run's requests.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

* ``{"loop": "open", "scenario": "poisson", "rate_fps": r}``:
  independent senders; requests are due on a Poisson schedule whatever
  the server does.
* ``{"loop": "closed", "clients": c}``: ``c`` requests outstanding at
  all times; each completion sends the next.

Every seed gets the same amount of work: an open loop sends exactly
``round(rate * seconds)`` requests, their schedule stretched or shrunk
to end inside the window, so only the order and the gaps change with
the seed. Each request names a frame of the seeded pool.

The Poisson schedule is a copy of the ``poisson`` case of
``make_scenario_schedule`` in ``repro.serving.traffic``, and
``pacing_report`` a copy of the one there, so that no change to the
program can move the yardstick.
"""

from __future__ import annotations

import dataclasses

import numpy as np

CLOSED_CYCLE = 1 << 16  # frame choices a closed loop cycles through


@dataclasses.dataclass
class Plan:
    loop: str                       # "open" | "closed"
    frame_idx: np.ndarray           # pool index of request i (cycled)
    offsets: np.ndarray | None = None   # open: due time of request i, s
    clients: int = 0                # closed: requests outstanding
    record: dict | None = None      # every resolved parameter


def poisson_times(n: int, rate_fps: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets of ``n`` Poisson requests at ``rate_fps``, the
    first at 0."""
    if n == 0 or rate_fps <= 0:
        return np.zeros(n)
    gaps = rng.exponential(scale=1.0 / rate_fps, size=n)
    return np.cumsum(gaps) - gaps[0]


def make_plan(traffic: dict, *, seed: int, seconds: float,
              pool: int) -> Plan:
    """The requests of one run, from the traffic file's parameters."""
    params = {k: v for k, v in traffic.items()
              if k not in ("loop", "what", "clients")}
    rng = np.random.default_rng(seed)
    if traffic["loop"] == "closed":
        clients = int(traffic["clients"])
        return Plan(loop="closed", clients=clients,
                    frame_idx=rng.integers(pool, size=CLOSED_CYCLE),
                    record={"loop": "closed", "clients": clients})
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    scenario = params.pop("scenario")
    rate = float(params.pop("rate_fps"))
    if scenario != "poisson" or params:
        raise ValueError(f"unknown open loop {scenario!r} {sorted(params)}")
    n = int(round(rate * seconds))
    times = poisson_times(n, rate, rng)
    # Stretch the schedule so that its n arrivals span the window at the
    # mean rate: the same number of requests for every seed.
    if n > 1 and times[-1] > 0:
        times = times * (seconds * (n - 1) / n / times[-1])
    return Plan(loop="open", offsets=times,
                frame_idx=rng.integers(pool, size=n),
                record={"loop": "open", "scenario": scenario,
                        "rate_fps": rate, "n": n})


def pacing_report(due: np.ndarray, submitted: np.ndarray) -> dict:
    """How late the generator ran: each request's submit time against
    its due time (seconds on the same clock)."""
    if len(due) == 0:
        return {"arrivals": 0}
    lag = np.asarray(submitted) - np.asarray(due)
    return {"arrivals": int(len(due)),
            "lag_ms_p50": float(np.percentile(lag, 50) * 1e3),
            "lag_ms_p95": float(np.percentile(lag, 95) * 1e3),
            "lag_ms_max": float(np.max(lag) * 1e3)}
