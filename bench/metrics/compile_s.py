"""Seconds XLA spent compiling during set-up (JAX's
``backend_compile_duration`` events); programs loaded from the persistent
cache count nothing.  Moves ``setup_s``."""


def read(run):
    return run.setup_compile_s
