"""Seconds from the start of the process to the stamp that opens the window:
imports, the native engine's build, making the state on the device, tracing
and lowering the step, compiling it (or the cache), the warm-up calls.  The
reference comparison runs after the window and is not in it."""


def read(run):
    return run.setup_s
