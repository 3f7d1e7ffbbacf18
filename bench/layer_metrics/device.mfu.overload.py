"""Useful FLOPs of in-deadline frames over (window x chips x one chip's bf16 peak)."""
from bench.stats import in_deadline


def read(rec):
    work = sum(rec["flops"][a.stream] for a in in_deadline(rec))
    return 100.0 * work / (rec["seconds"] * rec["chips"] * rec["peak"]) if work else None
