"""setup_trace_s: host seconds the worker spent tracing jit functions before
the window (program counter): ``dpow_engine_jit_seconds_total{phase="trace"}``
on the worker's registry at the window's start, in s. Most of it is the
engine's warm ladder, one trace of the Pallas kernel per launch shape."""

import re

SERIES = "dpow_engine_jit_seconds_total{"
PHASE = re.compile(r'(^|[{,])phase="trace"')


def read(w, name):
    values = [v for k, v in w.engine[0].items()
              if k.startswith(SERIES) and PHASE.search(k[len(SERIES) - 1:])]
    return sum(values) if values else None
