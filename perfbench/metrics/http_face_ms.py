"""http_face_ms: the server's HTTP face, per request (program counter): the
mean of stage ``accept`` (handler entry to auth, throttle, validation and
quota done) plus the mean of stage ``reply`` (the waiter resolved to the
reply validated), from the server's /metrics, in ms."""

import stages


def read(w, name):
    return stages.sum_ms(w.server, ("accept", "reply"))
