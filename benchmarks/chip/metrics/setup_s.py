"""setup_s: process start to the window's start: weights, engine,
warm-up and any compilation (host clock)."""


def read(run):
    return run.setup_s
