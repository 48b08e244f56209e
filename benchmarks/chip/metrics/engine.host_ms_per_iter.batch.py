"""Median host milliseconds of an engine iteration less its device waits
(``dooly.engine.execute`` less its ``engine.sync`` spans; batch)."""
from chipbench import spans
from chipbench.readers import of_trace

read = of_trace(spans.host_ms_per_iter)
