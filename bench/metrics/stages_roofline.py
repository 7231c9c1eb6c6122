"""The stage programs' share of their roofline: the least time the chips
need for the frames answered in the traced window, over the device busy
time summed over the chips. Least time per batch is, over the compute
layers, the larger of its int8 operations at the int8 peak and its bytes
(weights once, activations per frame) at HBM bandwidth
(``bench/workcount.py``), counted at int8 widths whatever route runs."""

from bench import workcount


def read(obs):
    t = obs.trace
    if t is None or obs.peaks is None or t["busy_s"] <= 0:
        return None
    frames = obs.completed_in_window()
    least = workcount.least_batch_s(obs.cfg, obs.batch, obs.peaks)
    return 100.0 * least * frames / obs.batch / (t["busy_s"] * obs.chips)
