"""Seconds of JAX tracing, lowering and backend compile or cache load
during set-up, summed from ``jax.monitoring`` duration events."""


def read(r):
    return r.compile_s
