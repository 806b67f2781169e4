"""The decode walk of a looped model against its roofline: the least time
for the cache rows the traced call's decode steps HAD to read (the live
rows of every (loop step, layer) entry, summed: ``last_stats["loop"]
["decode"]["cache_rows_read"]``; a key and a value of 2,048 lanes a row,
read once at the HBM peak, or a score and a value a row and head dimension
at the bf16 peak; ``benchmarks/costs_ouro.py``), over the summed device
time of the launch's events in the traced window.

Found by name: the Pallas launch carries its ``name=``
(``paged_heads_decode``, ``ops/pallas/paged_heads.py``), given the loop
step: one a layer and loop step a decode step, 192 in cell 8. The launch
copies whole blocks and its products compute every head against every
head's lanes: both read as a share under 100. A program without the
launch (the ``lax`` gather), or without the loop's tallies, reads
nothing."""

from benchmarks import costs_ouro
from benchmarks.metrics_common import kernel_events


def read(facts):
    decode = (facts.get("traced_loop") or {}).get("decode")
    events = kernel_events(facts, lambda name: "paged_heads_decode" in name)
    if not decode or not decode.get("cache_rows_read") or not events:
        return None
    least = costs_ouro.walk_floor_seconds(
        facts["config"], decode["cache_rows_read"], facts["peaks"])
    return 100.0 * least / (sum(ev.dur_ns for ev in events) / 1e9)
