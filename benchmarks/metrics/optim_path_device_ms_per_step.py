"""Device milliseconds a step spends on the fused optimizer path: packing
the weights, gradients and moments into flat buffers, the launches (one
for each group of one element type), and unpacking their results; summed
over the traced window and divided by the optimizer steps in it.

SELECTED BY SIZE, not by scope, until ``trace_reduce.read_xplane`` keeps an
event's ``op_name``. A launch is found by its NAME:
``ops/pallas/fused_optim.py`` calls its kernels ``fused_adamw`` /
``fused_adam`` / ``fused_sgd_mom``, and the device event of a kernel starts
with its instruction's name. The copies around it cannot be: in the compiled
step they carry ``op_name=".../optim/pack/..."`` and ``.../optim/unpack/...``
(the trainer's ``optim`` scope), but this JAX's trace names a device event
by the instruction WITHOUT its metadata, and an XLA instruction is called
after its primitive (``%concatenate.10``), so no event name holds the scope
(``PERF.md`` sections 3 and 7). They are told by what they move instead: an
operation belongs to the path if it reads or writes a buffer of the packed
size of ANY launch of the window, which the launches' own operands give
((rows, 128), or rows x 128 flat, in any element type). That finds the
``concatenate`` packs, the launches and the first operation on each of their
results; the per-leaf reshapes before a pack and the slices that cut the
results back into leaves are not found where a copy of another shape lies
between, and XLA's own converts of a packed result, which carry no
``op_name``, are counted.

The steps in the window are the events of the launch seen most often: every
group's launch is an instruction of its own (``%fused_adamw.1``,
``%fused_adamw.2``) that runs once an optimizer step."""

import collections
import re

from benchmarks.metrics_common import kernel_events

LAUNCH = re.compile(r"^(%?fused_(?:adamw|adam|sgd_mom)[.\d]*) = ")
PACKED = re.compile(r"\[(\d+),128\]")


def read(facts):
    launches = kernel_events(facts, LAUNCH.match)
    if not launches:
        return None
    steps = max(collections.Counter(
        LAUNCH.match(ev.name).group(1) for ev in launches).values())
    sizes = set()
    for ev in launches:
        for rows in PACKED.findall(ev.name):
            sizes.update((rows + ",128", str(int(rows) * 128)))
    packed = re.compile(r"\[(%s)\]" % "|".join(sorted(sizes)))
    path = kernel_events(facts, lambda name: bool(
        LAUNCH.match(name) or packed.search(name)))
    return sum(ev.dur_ns for ev in path) / 1e6 / steps
