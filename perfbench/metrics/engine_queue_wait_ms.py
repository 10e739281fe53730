"""engine_queue_wait_ms: mean wait of a job in the engine from submission to
its first device dispatch (program counter: Δsum/Δcount of
``dpow_engine_queue_wait_seconds`` over the window), in ms."""

import promtext


def read(w, name):
    v = promtext.mean_delta(w.engine[0], w.engine[1], "dpow_engine_queue_wait_seconds")
    return None if v is None else v * 1e3
