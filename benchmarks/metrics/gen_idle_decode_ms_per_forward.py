"""What the device waits for a decode forward: the busiest device's idle
seconds under the traced call's ``gen.decode_step`` or ``gen.block`` spans
(the plain and the block loop; children included), over the ``lm.dispatch``
records under them (a step's one forward, a block's denoising and store
forwards), in milliseconds. A loop that keeps the device fed reads next to
nothing; one that reads a forward's results before it launches the next
reads the round trip."""

from benchmarks import call_spans


def read(facts):
    return call_spans.decode_idle_ms_per_forward(facts)
