"""Scheduler / cache: slots that held a sequence per decode round,
``slot_rounds / (decode_steps * max_slots)`` from the engine's counters."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    steps = counters.get("decode_steps")
    if not steps or not counters.get("max_slots"):
        return None
    return 100.0 * counters["slot_rounds"] / (steps * counters["max_slots"])
