"""Set-up seconds: process start to the window's start (imports, the
device check, weights from the seed, compile or cache load, warm-up)."""


def read(run):
    return run.setup_s
