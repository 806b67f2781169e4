"""The busiest device's idle seconds of the traced window that lie under
NO ``gen.prefill``, ``gen.decode_step`` or ``gen.block`` of the traced call
(``gen.call``'s own time, ``gen.admit``, ``gen.release``, the runner's root
span, and what no span covers) over the call's seconds, in per cent. The
engine's regions are meant to hold every wait for the device: this says
how far they do. It also writes the call's whole table by innermost span,
and how well the two clocks agree, to standard error."""

from benchmarks import call_spans


def read(facts):
    idle = call_spans.idle_by_span(facts)
    if not idle:
        return None
    call_spans.note_table(idle)
    outside = sum(idle["idle_s"].values()) - call_spans.idle_under(
        idle, call_spans.REGIONS)
    return 100.0 * outside / idle["call_s"]
