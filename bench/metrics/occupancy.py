"""Mean share of serving slots held by a request at the window boundaries
inside the measured window (the scheduler's own counters, differenced
between window start and end), in %."""


def _sum(m):
    return m["occupancy_mean"] * max(m["windows"], 1)


def read(rec):
    n = rec.eng_end["windows"] - rec.eng_start["windows"]
    if n <= 0:
        return None
    slots = rec.cell.cfg["serving"]["max_slots"]
    return 100.0 * (_sum(rec.eng_end) - _sum(rec.eng_start)) / n / slots
