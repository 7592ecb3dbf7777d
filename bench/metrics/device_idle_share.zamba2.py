"""1 - busy / stretch, busy being the union of device operations in the
traced stretch."""
from bench.lib.readers import idle_share_pct as read  # noqa: F401
