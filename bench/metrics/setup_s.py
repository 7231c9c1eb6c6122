"""Seconds from process start to the start of the window: input draws,
host compile, build_server with its calibration pass, warm-up."""


def read(obs):
    return obs.setup_s
