"""Mean host time per tick of the front end (``serving/frontend.py``):
the self time of its ``pump``, ``route`` and ``harvest`` phases in each
of the window's tick events (``phase_s``, work since the previous tick
event).  A program whose tick events lack ``phase_s`` reads nothing."""

FRONT_END = ("pump", "route", "harvest")


def read(run):
    ticks = [t for t in run.window_telemetry() if "phase_s" in t]
    if not ticks:
        return None
    busy = sum(t["phase_s"].get(p, 0.0) for t in ticks for p in FRONT_END)
    return 1e3 * busy / len(ticks)
