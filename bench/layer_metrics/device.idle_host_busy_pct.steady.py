"""Device idle time while the host is inside serve.tick and outside
executor.block, over the traced tail, in %, mean over chips (spans.py)."""
from bench.stats import idle_host_busy_pct as read  # noqa: F401
