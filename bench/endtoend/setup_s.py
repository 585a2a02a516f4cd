"""Seconds from process start to the opening of the window: start-up,
compilation (or loading it from the compile cache), building and filling
the slab, inserting the corpus and warming the serve path."""


def value(run):
    return run.setup_s
