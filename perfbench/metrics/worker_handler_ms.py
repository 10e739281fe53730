"""worker_handler_ms: the worker's work handler, per request (program
counter): the mean of stage ``submit`` (work received to handed to the
engine) in the worker's registry, in ms."""

import stages


def read(w, name):
    return stages.mean_ms(w.engine, "submit")
