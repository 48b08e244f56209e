"""Median device milliseconds of the decode step program
(``jit_decode_step`` on the trace's ``XLA Modules`` line; chat)."""
from chipbench import spans
from chipbench.readers import of_trace

read = of_trace(lambda ev: spans.program_device_ms(ev, "decode_step"))
