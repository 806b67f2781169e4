"""The fused AdamW launch (``ops/pallas/fused_optim.py``) against its
roofline: the least time the chip could take for the bytes of the packed
weight, gradient and two moment buffers read and of the weight and moments
written (``costs.fused_adamw_bytes``; bandwidth-bound), over the summed
device time of the launch's events in the traced window.

No kernel passes ``name=`` to ``pallas_call``, and this JAX names a device
event by its whole HLO instruction, so the launch is told by its
signature: a ``tpu_custom_call`` whose first operand is the (1, 8) block
of scalars and whose buffers are packed (rows, 128)."""

import re

from benchmarks import costs
from benchmarks.metrics_common import kernel_events

LAUNCH = re.compile(r"custom-call\(f32\[1,8\].*custom_call_target=\"tpu_custom_call\"")
PACKED = re.compile(r"f32\[(\d+),128\]")


def read(facts):
    events = kernel_events(facts, LAUNCH.search)
    if not events:
        return None
    least = 0.0
    for ev in events:
        rows = int(PACKED.search(ev.name).group(1))
        least += costs.roofline_seconds(
            0.0, costs.fused_adamw_bytes(rows * 128), facts["peaks"])
    return 100.0 * least / (sum(ev.dur_ns for ev in events) / 1e9)
