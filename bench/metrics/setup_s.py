"""Set-up seconds: from process start to the start of the window
(weights made, the traffic's state filled, every shape warmed up, compiles
or cache loads included)."""


def read(run):
    return run.setup_s
