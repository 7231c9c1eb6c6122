"""The busiest stage worker's share of the window: the largest growth of
``stage_busy_s`` over the window, over all stages of all replicas,
divided by the window."""


def read(obs):
    deltas = [after - before
              for rb, ra in zip(obs.busy_before, obs.busy_after)
              for before, after in zip(rb, ra)]
    return 100.0 * max(deltas) / obs.window_s if deltas else None
