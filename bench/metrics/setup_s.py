"""Seconds from process start to the window's opening: start-up, weights,
compiles, warm-up, priming requests and the descent to the first rung."""


def read(run):
    return run.t0 - run.t_start
