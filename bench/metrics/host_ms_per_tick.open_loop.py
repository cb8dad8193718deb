"""``host_ms_per_tick`` in the open-loop cells, where the tail of the
token gaps is read at the 95th percentile (``itl_p95_ms``)."""

from bench.metrics.host_ms_per_tick import read  # noqa: F401
