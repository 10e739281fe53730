"""engine_solve_ms: the engine, from a job's first launch to its solve
applied on the host (program counter): the mean of stage ``device`` in the
worker's registry, in ms."""

import stages


def read(w, name):
    return stages.mean_ms(w.engine, "device")
