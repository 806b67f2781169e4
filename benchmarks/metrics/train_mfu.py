"""The whole step's share of the chips' bf16 peak: operations the forward
and backward passes need per token (``benchmarks/costs.py``: no optimizer,
nothing recomputed) times tokens per second of the window, over chips
times the peak."""


def read(facts):
    if "flops_per_token" not in facts:
        return None
    return (100.0 * facts["flops_per_token"] * facts["tokens_per_s"]
            / (facts["chips"] * facts["peaks"]["bf16_flops_per_s"]))
