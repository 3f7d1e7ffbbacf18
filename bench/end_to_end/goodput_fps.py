"""Frames due in the window that completed within their deadline, per second of window."""
from bench.stats import in_deadline


def read(rec):
    return len(in_deadline(rec)) / rec["seconds"]
