"""Set-up seconds: from the start of the process to the window's start —
imports, weights from the seed, engine, the store fill and the warm-up
(compilation too, where a program was not in the persistent cache)."""


def read(run):
    return run.setup_s
