"""Experts that got a route in a DECODE forward, a layer: the traced
call's ``last_stats["moe"]["by_phase"]["decode"]``, ``experts_hit`` over
forwards over expert layers (of a layer that holds a share: of the experts
held here). The weights a decode forward reads follow it; the totals mix
it with the prefill chunks, which hit nearly all. A program that does not
part the phases reads nothing."""


def read(facts):
    moe = facts.get("traced_moe") or {}
    decode = (moe.get("by_phase") or {}).get("decode")
    if not decode or not decode["forwards"]:
        return None
    cfg = facts["config"]
    layers = moe.get("layers") or (cfg["num_hidden_layers"]
                                   - cfg.get("first_k_dense_replace", 0))
    return decode["experts_hit"] / decode["forwards"] / layers
