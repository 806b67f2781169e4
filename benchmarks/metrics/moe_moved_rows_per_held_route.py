"""Rows that an expert layer holding a SHARE of its experts gathered into
its first grouped product, per route that fell on the experts held here:
the traced call's ``last_stats["moe"]["rows_moved"]`` over its ``routes``
(``parallel/moe.py`` ``moe_dropless`` under ``held``: passes x the slots of
a pass's tile layout, summed over layers and forwards). 1 is a layer that
touches its own routes alone; experts / held (32 as published over 32
chips) is one that sorts and gathers every route of every token to compute
its own. A program whose layer holds every expert counts no such rows:
nothing to read."""


def read(facts):
    moe = facts.get("traced_moe") or {}
    if not moe.get("rows_moved") or not moe.get("routes"):
        return None
    return moe["rows_moved"] / moe["routes"]
