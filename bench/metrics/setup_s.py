"""setup_s: process start to window open: build, weights, compiles (or
cache reads) and warm-up."""


def read(run):
    return run["setup_s"]
