"""Arrivals dropped by admission or a full queue, over arrivals offered (ServeMetrics counters)."""


def read(rec):
    adm = rec["report"]["admission"]
    return 100.0 * adm["dropped"] / adm["offered"] if adm["offered"] else None
