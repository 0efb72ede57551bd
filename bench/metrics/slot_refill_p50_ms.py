"""Median time a serving slot stands empty between two requests: from the
end of the ``engine.finish`` span that frees it to the start of the
``engine.merge`` span that fills it (``Engine.metrics()["slot_refills"]``,
on the host clock), over the refills whose merge lies in the window, in
ms."""

from bench.lib import stats


def read(rec):
    refills = rec.eng_end.get("slot_refills")
    if refills is None:
        return None
    gaps = [gap for t, gap in refills if rec.in_window(t)]
    if not gaps:
        return None
    return 1e3 * stats.percentile(gaps, 50)
