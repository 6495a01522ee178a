"""device_idle_pct: 100 × (1 − busy / window) of the traced stretch,
busy being the union of the chip's device operations; on several chips
the mean of each chip's share.  Read for every ``device_idle_pct.<group>``."""

from tracereduce import run_idle_pct as read  # noqa: F401
