"""Whole engine step's share of the bf16 peak, in per cent (batch)."""
from chipbench.readers import step_mfu as read  # noqa: F401
