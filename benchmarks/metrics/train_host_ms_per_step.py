"""Host milliseconds inside one ``ShardedTrainer.step`` call (enqueue
only), mean over the window's calls."""


def read(facts):
    calls = facts.get("step_call_seconds")
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
