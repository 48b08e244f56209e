"""Per cent of the traced window with no operation on the device (batch)."""
from chipbench.readers import idle_share as read  # noqa: F401
