"""Device-to-host bytes per sweep (the program's ``dse.transfer_bytes``
counter, read around each sweep before any cache clear resets it)."""

from chipbench.readers import d2h_bytes_per_step as read  # noqa: F401
