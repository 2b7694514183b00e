"""Host wall time of the engine's chunk calls per scan step they ran:
sum of ``sweep.execute`` span time over the sum of their ``scan_steps``."""
from bench.lib.readers import per_step_us as read  # noqa: F401
