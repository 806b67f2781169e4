"""The decode step against the chip's peaks: the least time the chip could
take for one step (every weight and the live keys and values read once at
the HBM peak, or 2 x parameters x rows operations at the bf16 peak,
whichever is longer; ``benchmarks/costs.py``) over the measured time a step,
all decode steps of the window."""


def read(facts):
    if not facts.get("decode_steps") or not facts.get("decode_seconds"):
        return None
    measured = facts["decode_seconds"] / facts["decode_steps"]
    return 100.0 * facts["decode_step_floor_s"] / measured
