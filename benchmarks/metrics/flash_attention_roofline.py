"""The flash-attention kernel (``ops/pallas/flash_attention.py``), forward
and backward, against its roofline: the least time for its operations and
bytes (``costs.flash_attention_flops`` / ``_bytes``; backward counts dv, dp,
dq, dk and not the recomputed scores) over the summed device time of its
events in the traced window.

Told by signature, as no kernel passes ``name=``: a ``tpu_custom_call``
whose first operand is a bfloat16 (batch x heads, T, D) block. A forward
call also returns the float32 row statistics; a call that returns only
bfloat16 blocks belongs to the backward pass, however many calls that pass
is split into. A step has one backward pass for each forward call."""

import re

from benchmarks import costs
from benchmarks.metrics_common import kernel_events

CALL = re.compile(r"custom-call\(bf16\[(\d+),(\d+),(\d+)\]\{.*"
                  r"custom_call_target=\"tpu_custom_call\"")


def read(facts):
    events = kernel_events(facts, CALL.search)
    if not events:
        return None
    forward = [ev for ev in events
               if "f32[" in ev.name.split(" custom-call(", 1)[0]]
    if not forward:
        return None
    least = 0.0
    for ev in forward:
        n, t, d = (int(x) for x in CALL.search(ev.name).groups())
        passes = (False, True) if len(events) > len(forward) else (False,)
        for backward in passes:
            least += costs.roofline_seconds(
                costs.flash_attention_flops(n, 1, t, d, backward),
                costs.flash_attention_bytes(n, 1, t, d, backward),
                facts["peaks"])
    return 100.0 * least / (sum(ev.dur_ns for ev in events) / 1e9)
