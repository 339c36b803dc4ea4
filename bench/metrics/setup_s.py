"""Seconds from process start to the window's start: drawing the weights
on the chip, building the program, compiling or loading every program
the cell runs, and warming each shape."""


def read(run):
    return run.setup_s
