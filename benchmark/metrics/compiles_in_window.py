"""Planner + fusion layer: programs that went through the backend's compile
step between the window's start and its end (a fetch from the persistent
cache counts too), by ``jax.monitoring``'s events. Should read 0: warm-up
has met every shape."""


def read(facts: dict):
    return facts["compiles_in_window"]
