"""Share of the traced window in which no operation ran on the chip."""

from chipbench.readers import idle_share as read  # noqa: F401
