"""Self time of the program's serve.route spans (the fleet router's decision for
each offered frame) in the traced tail, per frame completed inside it; nothing
to read where the program records no such span."""
from bench.stats import span_self_ms_per_frame


def read(rec):
    return span_self_ms_per_frame(rec, "serve.route")
