"""Frames answered inside the window, over the window's length."""


def read(obs):
    return obs.completed_in_window() / obs.window_s
