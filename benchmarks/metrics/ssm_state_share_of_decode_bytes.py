"""The recurrent state's share of what a hybrid state-space model's decode
step has to move: each row's float32 state and conv window of every Mamba
layer, read and written once a step, over those bytes plus the weights
(the Mamba and attention layers', an expert layer's router, shared expert
and the experts a decode step hit, the head, the rows' embeddings) and the
attention layers' cached rows read (``costs_nemotron_h.decode_step_bytes``;
the rows and cached rows a step from the window's ``last_stats["ssm"]
["decode"]``, the experts hit from its decode forwards). Counted on the
host. It bounds what any change to the state's update can give the cell.
A program without the state tallies reads nothing."""

from benchmarks import costs_nemotron_h


def read(facts):
    decode = (facts.get("ssm") or {}).get("decode")
    if not decode or not decode.get("forwards"):
        return None
    steps = decode["forwards"]
    weights, state, cached = costs_nemotron_h.decode_step_bytes(
        facts["config"], decode["rows"] / steps,
        facts["decode_experts_hit_per_layer"],
        decode["cache_rows_read"] / steps)
    return 100.0 * state / (weights + state + cached)
