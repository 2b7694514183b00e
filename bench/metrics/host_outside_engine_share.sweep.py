"""Share of the window's wall time outside the engine's chunk calls
(``sweep.compile`` / ``sweep.execute`` spans): experiment set-up, lane
building, metrics and store writes on the host."""
from bench.lib.readers import outside_share as read  # noqa: F401
