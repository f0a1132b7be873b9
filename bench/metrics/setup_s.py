"""Set-up: process start to the window: loading, weights, packing,
compiling or loading compiled programs, and warming the shapes."""


def read(run):
    return run.setup_s
