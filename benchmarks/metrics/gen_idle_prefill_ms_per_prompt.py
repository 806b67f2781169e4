"""What the device waits for a prompt: the busiest device's idle seconds
under the traced call's ``gen.prefill`` spans (their children included: a
chunk's ``lm.dispatch``, the ``lm.fetch`` that drains the last chunk's
loads, ``kv.sync``), over the prompts, in milliseconds. The records are
the call's kept journey on the trace's clock (``benchmarks/call_spans.py``).
A batched prefill of several prompts a forward would shorten it."""

from benchmarks import call_spans


def read(facts):
    idle = call_spans.idle_by_span(facts)
    prompts = idle and call_spans.count_under(idle, "gen.prefill",
                                              ("gen.prefill",))
    if not prompts:
        return None
    return 1e3 * call_spans.idle_under(idle, ("gen.prefill",)) / prompts
