"""Set-up: from process start to the window's opening (loading, weights,
engine, compilation or its cache, warm-up and the mix's pre-roll)."""


def read(run):
    return run.setup_s
