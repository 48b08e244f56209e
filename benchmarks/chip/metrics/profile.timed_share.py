"""Per cent of the traced profile pass inside the oracle's timed repeats
(``dooly.oracle.timed`` spans, their union)."""
from chipbench import spans
from chipbench.readers import of_trace

read = of_trace(lambda ev: spans.span_share(ev, "oracle.timed"))
