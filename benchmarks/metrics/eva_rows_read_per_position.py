"""Cache rows a decode forward read a layer, per position of context: the
engine's ``last_stats["eva"]["decode"]``, ``window_rows_read +
summary_rows_read`` over ``positions``, summed over the window's calls
(counted on the host from the cache's lengths). 1 is a cache that keeps
and reads every row; a window of 2,048 exact rows beside a summary a chunk
of 16 reads some 0.2 at contexts of 7k. A program without the tallies
reads nothing."""


def read(facts):
    decode = (facts.get("eva") or {}).get("decode")
    if not decode or not decode.get("positions"):
        return None
    return ((decode["window_rows_read"] + decode["summary_rows_read"])
            / decode["positions"])
