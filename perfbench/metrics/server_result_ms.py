"""server_result_ms: the server's result path, from the winning result
decoded to the waiter's future resolved (program counter): the mean of stage
``winner`` (store reads, validation, the election's setnx) plus the mean of
stage ``resolve`` (the work stored), from the server's /metrics, in ms."""

import stages


def read(w, name):
    return stages.sum_ms(w.server, ("winner", "resolve"))
