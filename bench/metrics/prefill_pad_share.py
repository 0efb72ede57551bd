"""Share of the admission prefill's positions that are padding: each wave
is padded to a power-of-two (rows, length) bucket.  100 x (1 - real
prompt tokens / bucketed positions) of the prefills dispatched in the
window (``Engine.metrics()`` counters, differenced), in %."""


def read(rec):
    a, b = rec.eng_start, rec.eng_end
    if "prefill_positions" not in b:
        return None
    positions = b["prefill_positions"] - a["prefill_positions"]
    if positions <= 0:
        return None
    return 100.0 * (1.0 - (b["prefill_tokens"] - a["prefill_tokens"])
                    / positions)
