"""Seconds from process start to the window's start: imports, inputs,
the program's build, warm-up and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
