"""Output tokens emitted inside the window over the window's seconds: all
the work over all the time (host stamps of each token's delivery)."""


def read(rec):
    n = sum(rec.in_window(t) for r in rec.live for t in r.times)
    return n / rec.window_s
