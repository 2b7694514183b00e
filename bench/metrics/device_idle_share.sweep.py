"""Share of the profiled stretch in which no operation ran on the chip:
1 - (union of device-op intervals / stretch), from the profiler trace."""
from bench.lib.readers import idle_share as read  # noqa: F401
