"""XLA compiles between window start and end in a serving cell (expected 0)."""
from harness.readers import compiles_in_window as read  # noqa: F401
