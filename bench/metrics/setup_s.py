"""setup_s: process start to window open, compiles and warm-up included
(host clock)."""


def read(run):
    return run.setup_s
