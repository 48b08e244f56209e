"""Per cent of the traced window with no operation on the device (chat)."""
from chipbench.readers import idle_share as read  # noqa: F401
