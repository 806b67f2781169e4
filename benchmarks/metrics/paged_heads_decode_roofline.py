"""The decode walk over the window and summary groups against its
roofline: the least time for the cache rows a decode step HAD to read (the
live window rows and the summaries of the traced call's decode forwards,
a key and a value of every head a row and layer, read once at the HBM
peak; or a score and a value a row and head dimension at the bf16 peak;
``benchmarks/costs_evabyte.py``), over the summed device time of the
launch's events in the traced window.

Found by name: the Pallas launch carries its ``name=``
(``paged_heads_decode``, ``ops/pallas/paged_heads.py``), two a layer a
decode step, one a group. The rows are the traced call's own, from the
engine's ``last_stats["eva"]["decode"]`` less what its closings read (a
closing is no walk). The launch copies whole blocks and its products
compute every head against every head's lanes: both read as a share
under 100. A program without the launch (the ``lax`` gather) reads
nothing."""

from benchmarks import costs_evabyte
from benchmarks.metrics_common import kernel_events


def read(facts):
    decode = (facts.get("traced_eva") or {}).get("decode")
    events = kernel_events(facts, lambda name: "paged_heads_decode" in name)
    if not decode or not events:
        return None
    cfg = facts["config"]
    rows = (decode["window_rows_read"] + decode["summary_rows_read"]
            - decode["windows_closed"] * cfg["window_size"]
            - decode["forwards"] * len(facts["traffic"]["prompt_lens"]))
    least = costs_evabyte.walk_floor_seconds(cfg, rows, facts["peaks"])
    return 100.0 * least / (sum(ev.dur_ns for ev in events) / 1e9)
