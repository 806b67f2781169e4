"""Share of the block loop's time spent in the pass that commits a block:
summed ``gen.block_store`` span time (the forward over the final tokens and
the appends of its K and V) over summed ``gen.block`` span time, the
window's calls of a traced run."""


def read(facts):
    spans = facts.get("block_span_seconds") or {}
    block = sum(spans.get("gen.block", ()))
    if not block or not spans.get("gen.block_store"):
        return None
    return 100.0 * sum(spans["gen.block_store"]) / block
