"""The decode update of the state-space layers against its roofline: the
least time for the bytes the traced call's decode steps HAD to move
through ``ssm_state_step`` (each fed row's float32 state read and written
once a Mamba layer, its dt x, decay, B and C in and y out, at the HBM
peak: ``benchmarks/costs_nemotron_h.py`` ``ssm_step_floor_seconds``, the
rows from ``last_stats["ssm"]["decode"]["rows"]``), over the summed device
time of the launch's events in the traced window.

Found by name: the Pallas launch carries its ``name=`` (``ssm_state_step``,
``ops/pallas/ssm_state.py``), one a Mamba layer a decode step. A program
without the launch (the ``lax`` path), or without the state tallies, reads
nothing."""

from benchmarks import costs_nemotron_h
from benchmarks.metrics_common import kernel_events


def read(facts):
    decode = (facts.get("traced_ssm") or {}).get("decode")
    events = kernel_events(facts, lambda name: "ssm_state_step" in name)
    if not decode or not decode.get("rows") or not events:
        return None
    least = costs_nemotron_h.ssm_step_floor_seconds(
        facts["config"], decode["rows"], facts["peaks"])
    return 100.0 * least / (sum(ev.dur_ns for ev in events) / 1e9)
