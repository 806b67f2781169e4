"""Cache positions a row commits over the block forwards a row takes, from
the engine's ``last_stats`` summed over the window's calls: the block
length over the forwards a block costs (4 / 3 with two denoising steps and
a store pass). A scheduler that folds the store pass into the next block's
first step moves it; so does a schedule that needs fewer steps."""


def read(facts):
    forwards = facts.get("block_row_forwards")
    if not forwards:
        return None
    return facts["block_positions_committed"] / forwards
