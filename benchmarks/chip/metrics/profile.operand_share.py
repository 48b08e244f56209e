"""Per cent of the traced profile pass spent drawing operands
(``dooly.profile.operands`` spans, their union)."""
from chipbench import spans
from chipbench.readers import of_trace

read = of_trace(lambda ev: spans.span_share(ev, "profile.operands"))
