"""Device time of custom-call (Mosaic kernel) events over device busy time,
serving cells."""
from harness.readers import mosaic_dev_pct as read  # noqa: F401
