"""Set-up: process start to the start of the measured window (loading,
input generation, warm-up and, in a run that compiles, compilation)."""


def read(ctx):
    return ctx["setup_s"]
