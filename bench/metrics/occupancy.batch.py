"""Mean share of the engine's slots occupied at each decode chunk of the
window, from the engine's own ``engine_slot_occupancy_ratio`` histogram
(program counter)."""


def read(run):
    chunks, total = run.occupancy
    return 100.0 * total / chunks if chunks else None
