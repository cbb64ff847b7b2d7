"""Executables built or loaded inside the window (JAX's backend-compile
events, counted by the launcher's ``jax.monitoring`` listener): a request
that waits on one pays a compile or a cache load."""


def read(rec: dict):
    return rec["launcher"].get("compiles")
