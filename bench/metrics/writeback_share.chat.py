"""Share of the traced stretch spent copying finished or preempted
requests' KV rows to the host (summed ``serve.writeback`` spans)."""
from bench.metrics._spans import writeback_share as read  # noqa: F401
