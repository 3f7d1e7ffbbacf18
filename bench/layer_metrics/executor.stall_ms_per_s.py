"""Milliseconds per second of window spent in passes of the window's loop
longer than ``harness.STALL_S`` (host clock): the waits inside ``server.tick``
that hold every stream and make admission drop frames."""


def read(rec):
    st = [s["wall_s"] for s in rec["stalls"] if s["at_s"] < rec["seconds"]]
    return 1e3 * sum(st) / rec["seconds"]
