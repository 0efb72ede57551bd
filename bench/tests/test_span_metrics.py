"""The readers of the engine's refill spans and prefill counters, on
hand-made run records."""

import pytest

from bench.tests.test_metrics import _record, read


def test_slot_refill_median_of_the_refills_merged_in_the_window():
    rec = _record([])
    # (merge time, seconds empty): one before the window, three in it,
    # one at its end (outside)
    rec.eng_end = dict(rec.eng_end, slot_refills=[
        [99.0, 50.0], [101.0, 2.0], [105.0, 11.0], [109.9, 4.0],
        [110.0, 70.0]])
    assert read("slot_refill_p50_ms", rec) == pytest.approx(4000.0)


def test_prefill_pad_share_differences_the_counters():
    rec = _record([])
    rec.eng_start = dict(rec.eng_start, prefill_tokens=100,
                         prefill_positions=128)
    rec.eng_end = dict(rec.eng_end, prefill_tokens=100 + 3000,
                       prefill_positions=128 + 4096)
    assert read("prefill_pad_share", rec) == pytest.approx(
        100 * (1 - 3000 / 4096))


def test_readers_stay_silent_without_the_engine_counters():
    """An engine that keeps neither counter reads nothing; nor does a
    window with no refill merged or no prefill dispatched."""
    rec = _record([])
    assert read("slot_refill_p50_ms", rec) is None
    assert read("prefill_pad_share", rec) is None
    rec.eng_end = dict(rec.eng_end, slot_refills=[[50.0, 1.0]],
                       prefill_tokens=7, prefill_positions=8)
    rec.eng_start = dict(rec.eng_start, prefill_tokens=7,
                         prefill_positions=8)
    assert read("slot_refill_p50_ms", rec) is None
    assert read("prefill_pad_share", rec) is None
