"""Mean real frames per flight, over completed frames (ServeMetrics.mean_effective_batch)."""


def read(rec):
    return rec["report"]["batching"]["mean_effective_batch"]
