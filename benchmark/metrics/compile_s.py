"""Seconds JAX spent compiling (or loading from the persistent cache)
during set-up: its backend_compile_duration events."""


def read(r):
    return r.compile_s if r.compile_s > 0 else None
