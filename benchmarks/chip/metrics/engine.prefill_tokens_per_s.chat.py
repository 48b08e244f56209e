"""Prompt tokens per engine second of the iterations with prefill (chat)."""
from chipbench.readers import prefill_tokens_per_s as read  # noqa: F401
