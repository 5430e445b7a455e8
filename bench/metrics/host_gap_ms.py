"""Mean host time between ticks in which the device has no tick queued
(engine tick, ``serving/engine.py``): over consecutive tick events of the
window, the next tick's ``dispatch_t`` (its jitted call returned) less
this tick's ``ready_t`` (its sync returned), from the program's own tick
events.  A program whose tick events lack those times reads nothing."""


def read(run):
    ticks = run.window_telemetry()
    gaps = [b["dispatch_t"] - a["ready_t"] for a, b in zip(ticks, ticks[1:])
            if b["tick"] == a["tick"] + 1
            and a.get("ready_t") is not None
            and b.get("dispatch_t") is not None]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
