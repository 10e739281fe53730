"""broker_round_trip_ms: the broker hop out and back, per request (program
counter): the server's mean of stage ``result_in`` (publish to the winning
result decoded) less the worker's means of ``pack``, ``device`` and
``result`` (work received to result published), in ms. The rest of the
server's wait is the broker, both ways, and the worker's decode."""

import stages


def read(w, name):
    server = stages.mean_ms(w.server, "result_in")
    worker = stages.sum_ms(w.engine, ("pack", "device", "result"))
    if server is None or worker is None:
        return None
    return server - worker
