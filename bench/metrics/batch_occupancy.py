"""Mean share of the engine's slots holding a request, over the program's
own tick events in the window (scheduler, ``serving/scheduler.py``)."""


def read(run):
    ticks = run.window_telemetry()
    if not ticks:
        return None
    slots = run.engine["max_slots"]
    return 100.0 * sum(t["live_slots"] for t in ticks) / (len(ticks) * slots)
