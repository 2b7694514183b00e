"""Plain reference of the scheduling semantics the benchmark checks against.

It imports nothing of the program under test: the job trace, the
rigid->malleable transform, the event-driven EASY/malleable scheduler and
the paper's metrics are written here again, in plain numpy and Python,
from the paper (arXiv 2602.17318, sections 2.1-2.3) and the
configuration files under ``bench/configs``.
"""
