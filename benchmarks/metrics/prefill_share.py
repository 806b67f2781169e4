"""Prefill seconds over prefill + decode seconds, from the engine's
``last_stats``, summed over the window's calls."""


def read(facts):
    prefill, decode = facts.get("prefill_seconds"), facts.get("decode_seconds")
    if prefill is None or not (prefill + decode):
        return None
    return 100.0 * prefill / (prefill + decode)
