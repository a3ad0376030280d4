"""End to end: process start to the start of the window.  Imports, the
backend, the seeded pool, ``prepare_training(warmup=True)`` with its
compiles, the seeded weights and the first steps.  Host clock."""


def read(ctx):
    return ctx["setup_s"]
