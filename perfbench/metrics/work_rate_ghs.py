"""work_rate_ghs: capacity as a hub operator feels it (host clock).

For every request whose valid work came back inside the window, the expected
effort of its class threshold, 2^64 / (2^64 - d); their sum over the window's
seconds, in Ghash/s. It counts work delivered, not nonces scanned."""

import refcheck


def read(w, name):
    done = w.completed_in_window()
    if not done:
        return None
    effort = sum(refcheck.expected_effort(w.thresholds[r["cls"]]) for r in done)
    return effort / w.seconds / 1e9
