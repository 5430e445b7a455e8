"""Mean share of the usable KV pages in use, over the program's own tick
events in the window (cache manager, ``serving/blocks.py``; the null
page is not usable)."""


def read(run):
    ticks = run.window_telemetry()
    if not ticks:
        return None
    usable = run.engine["num_blocks"] - 1
    return 100.0 * sum(t["pool_in_use"] for t in ticks) / (len(ticks)
                                                           * usable)
