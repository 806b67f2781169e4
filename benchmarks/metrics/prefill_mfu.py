"""The prefill against the chip's bf16 peak: the operations of prefilling
the window's prompts (``benchmarks/costs_xing4.py``: a token's products,
the expanded attention over the causal half, the re-expansion of cached
rows that the engine's ``last_stats["mla"]`` counted; the last layer's
rows need its ``kv_a`` projection alone) over the prefill seconds of the
engine's ``last_stats``, summed over the window's calls. It bounds a later
claim on the prefill half as ``gen_mfu`` bounds one on the decode half."""


def read(facts):
    flops, seconds = facts.get("prefill_flops"), facts.get("prefill_seconds")
    if not flops or not seconds:
        return None
    return 100.0 * flops / seconds / facts["peaks"]["bf16_flops_per_s"]
