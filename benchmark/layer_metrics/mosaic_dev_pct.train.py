"""Device time of custom-call (Mosaic kernel) events over device busy time,
training cells."""
from harness.readers import mosaic_dev_pct as read  # noqa: F401
