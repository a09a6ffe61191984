"""Seconds from the start of the process to the start of the window:
imports, input generation, building the program, warming its shapes
(compiles included where the cache lacks them)."""


def read(ctx):
    return ctx["setup_s"]
