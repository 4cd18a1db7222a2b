"""Share of the traced steady segment with no operation on the device (mean
over devices), training cells."""
from harness.readers import idle_pct as read  # noqa: F401
