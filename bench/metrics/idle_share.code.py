"""Per cent of the traced window with no op on the device (trace)."""

from bench.metrics._shared import idle_share as read  # noqa: F401
