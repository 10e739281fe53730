"""work_p50_ms: median time to valid work (host clock), from the intended
send, over every judged request intended inside the window; a request with
no valid work counts as a miss (latencyof.py)."""

import latencyof


def read(w, name):
    return latencyof.percentile_ms(w, 50)
