"""One minus device busy over the traced window, averaged over the cell's chips."""
from bench.stats import idle_pct as read  # noqa: F401
