"""Share of the traced steady segment with no operation on the device,
serving cells."""
from harness.readers import idle_pct as read  # noqa: F401
