"""The cache's share of what a looped model's decode step has to read:
the live cache rows a step read (the window's ``last_stats["loop"]
["decode"]["cache_rows_read"]`` over its decode forwards, summed over
every (loop step, layer) entry; a key and a value a row) over those bytes
plus every layer's weights once a loop step, the head and the rows'
embeddings (``costs_ouro.decode_step_bytes``). Counted on the host from
the cache's lengths. It bounds what any change to the walk can give the
cell: the rest of the step's bytes are weights. A program without the
tallies reads nothing."""

from benchmarks import costs_ouro


def read(facts):
    decode = (facts.get("loop") or {}).get("decode")
    if not decode or not decode.get("forwards"):
        return None
    weights, cached = costs_ouro.decode_step_bytes(
        facts["config"], len(facts["traffic"]["prompt_lens"]),
        decode["cache_rows_read"] / decode["forwards"])
    return 100.0 * cached / (weights + cached)
