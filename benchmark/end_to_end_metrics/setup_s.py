"""Seconds from the start of the process to the start of the window:
imports, the native build where it is missing, weights and batches,
tracing, compiling or reading the cache, the reference check, warm-up."""


def read(run):
    return run.measured["setup_s"]
