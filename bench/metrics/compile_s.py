"""Seconds of XLA backend compile during set-up (persistent-cache reads
included), from JAX's ``backend_compile_duration`` events."""


def read(layer):
    return layer.get("compile_s")
