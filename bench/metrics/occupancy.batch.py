"""Mean share of the engine's slots occupied at each decode chunk of the
window, from the engine's own ``engine_slot_occupancy_ratio`` histogram
(program counter): its sum over its count, taken over the window from
``Run.counters``."""


def read(run):
    h = run.counters.get("engine_slot_occupancy_ratio")
    return 100.0 * h["total"] / h["count"] if h and h["count"] else None
