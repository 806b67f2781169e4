"""Bytes of the K and V pools the engine hands to one forward from the
host, counted from the cache's own arrays."""


def read(facts):
    return facts.get("kv_host_bytes_per_step") or None
