"""setup_s: process start to the start of the measured window (host clock):
server and worker start, the engine's warm ladder (compiles on a cold
cache), and the short run of traffic before the window."""


def read(w, name):
    return w.setup_s
