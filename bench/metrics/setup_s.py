"""Process start to window start, in s: loading, weights, compiling or
loading compiled programs, warm-up and the traffic's prelude."""


def read(rec):
    return rec.setup_s
