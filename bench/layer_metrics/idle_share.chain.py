"""1 - device busy / traced window, in %."""
from bench.rooflines import idle_share as read  # noqa: F401
