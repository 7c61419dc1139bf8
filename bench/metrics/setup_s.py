"""Seconds from the benchmark's start to the window's: loading, making the
weights, compiling and warming every shape the window uses."""


def read(run):
    return run.setup_s
