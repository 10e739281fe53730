"""scan_useful_share.<rate|tail>: share of the nonces the engine scanned that
bought delivered work (program counter).

The expected effort of every valid work delivered in the window (all
classes, background included) over the window's growth of the engine's
``dpow_engine_hashes_total``, in %. Scan past a hit inside a window, rows
scanned for requests already solved, and misses all lower it."""

import promtext
import refcheck


def read(w, name):
    scanned = promtext.delta(w.engine[0], w.engine[1], "dpow_engine_hashes_total")
    if scanned <= 0:
        return None
    effort = sum(refcheck.expected_effort(w.thresholds[r["cls"]])
                 for r in w.completed_in_window())
    return 100.0 * effort / scanned
