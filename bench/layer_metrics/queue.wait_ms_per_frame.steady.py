"""Mean wait of the window's completed frames from submit to admission into a
flight: stream queue and coalescer hold (ServeMetrics queue counter)."""


def read(rec):
    q = rec["report"].get("queue")
    return q["wait_ms_mean"] if q and q["frames"] else None
