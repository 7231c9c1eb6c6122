"""The whole served step's share of the chips' int8 peak: the operations
(two per MAC, ``bench/workcount.py``) of the frames answered in the
window, over window x chips x int8 peak."""

from bench import workcount


def read(obs):
    if obs.peaks is None:
        return None
    ops = 2 * workcount.macs_per_frame(obs.cfg) * obs.completed_in_window()
    return 100.0 * ops / (obs.window_s * obs.chips
                          * obs.peaks["int8_ops_per_s"])
