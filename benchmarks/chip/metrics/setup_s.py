"""Seconds from the process's start to the end of warm-up: imports, the
program's set-up, weights, compilation (or the compile cache's reads)."""


def read(ctx):
    return ctx["setup_s"]
