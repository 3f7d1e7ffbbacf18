"""Useful FLOPs of completed frames over (the sum of their latencies x one chip's bf16 peak)."""
from bench.stats import completed


def read(rec):
    done = completed(rec)
    busy = sum(a.latency_due_s for a in done)
    return 100.0 * sum(rec["flops"][a.stream] for a in done) / (busy * rec["peak"]) if busy else None
