"""Seconds inside compilation or loads from the persistent cache, summed
from JAX's own monitoring events over the whole run."""


def read(facts):
    return facts.get("compile_s")
