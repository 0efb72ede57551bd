"""Host seconds the engine's main thread spends per decode window inside
the measured window, in ms: its dispatch, admission-merge and bookkeeping
stages from ``Engine.metrics()["profile"]`` (host wall clock), without
the harvest stage, which is the wait for the device."""

STAGES = ("dispatch", "admission_stage", "bookkeep")


def read(rec):
    n = rec.eng_end["windows"] - rec.eng_start["windows"]
    if n <= 0:
        return None
    a = rec.eng_start["profile"]["seconds"]
    b = rec.eng_end["profile"]["seconds"]
    return 1e3 * sum(b[s] - a[s] for s in STAGES) / n
