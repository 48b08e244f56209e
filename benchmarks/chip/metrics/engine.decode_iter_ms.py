"""Median engine milliseconds of the decode-only iterations."""
from chipbench.readers import decode_iter_ms as read  # noqa: F401
