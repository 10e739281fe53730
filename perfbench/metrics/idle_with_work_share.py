"""idle_with_work_share.<rate|tail>: share of the traced window in which the
chip ran nothing while the engine held work (device trace), in %.

Each gap of the device plane is labelled by the host span that overlaps it
most (``xplane.reduce``). The engine's loop sits in ``dpow.engine.idle``
only while it holds no job, so every other label is idle with work: a
launch in readback, an apply, a dispatch. Summed per chip over the window,
averaged over the chips used. The rest of ``device_idle_share`` is idle for
lack of demand. A program without the engine's spans (no gap labelled
``dpow.*``) cannot tell the two apart: nothing is read."""

ENGINE_IDLE = "dpow.engine.idle"


def read(w, name):
    t = w.trace
    if not t or not t.get("devices") or t["window_s"] <= 0:
        return None
    idle = [d["idle"] for d in t["devices"]]
    if any(v > 0 for i in idle for v in i.values()) and not any(
            label.startswith("dpow.") for i in idle for label in i):
        return None
    with_work = [sum(v for label, v in i.items() if label != ENGINE_IDLE) for i in idle]
    return 100.0 * sum(with_work) / len(with_work) / t["window_s"]
