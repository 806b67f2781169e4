"""Cached latent rows that prefill chunks up-projected again, per prompt
token committed: the engine's ``last_stats["mla"]["expanded_rows"]`` over
its ``prefill_tokens``, summed over the window's calls. The price of
prefilling in chunks: some L / 2c for a prompt of L in chunks of c, 0 for a
prompt that is one chunk."""


def read(facts):
    mla, tokens = facts.get("mla"), facts.get("prefill_tokens")
    if not mla or not tokens:
        return None
    return mla["expanded_rows"] / tokens
