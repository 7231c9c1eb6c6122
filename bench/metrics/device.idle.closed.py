"""Device idle share of the traced window: 1 minus the union of device
operation intervals over the window, averaged over the cell's chips
(the closed-loop cells)."""

from bench.trace import idle_percent


def read(obs):
    return idle_percent(obs.trace)
