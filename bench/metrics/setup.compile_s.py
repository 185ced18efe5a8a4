"""Seconds of tracing, lowering and compiling (or loading from the
persistent cache) during set-up, from ``jax.monitoring`` events."""


def read(facts):
    return facts.get("setup_compile_s")
