"""admission_wait_ms: mean wait of an on-demand dispatch in the server's
admission queue (program counter: Δsum/Δcount of
``dpow_sched_queue_wait_seconds`` from the server's /metrics), in ms."""

import promtext


def read(w, name):
    v = promtext.mean_delta(w.server[0], w.server[1], "dpow_sched_queue_wait_seconds")
    return None if v is None else v * 1e3
