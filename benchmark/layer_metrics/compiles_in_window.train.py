"""XLA compiles between window start and end in a training cell (expected 0:
anything else means a shape was not warmed and the window waited for it)."""
from harness.readers import compiles_in_window as read  # noqa: F401
