"""Self time of the program's executor.dispatch spans (segment calls, placement
excluded) in the traced tail, summed over replicas, per frame completed inside it."""
from bench.stats import span_self_ms_per_frame


def read(rec):
    return span_self_ms_per_frame(rec, "executor.dispatch")
