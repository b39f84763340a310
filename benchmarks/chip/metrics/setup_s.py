"""Seconds from process start to the window: imports, device start,
weights, build, compile or cache load, warm-up, traffic inputs."""


def read(run):
    return run.setup_s
