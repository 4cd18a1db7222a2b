"""Device memory held on the chip when the window closes, over
``bytes_limit``, serving cells: the allocator's live buffers plus what it
has reserved for the engine's loaded programs, or its peak of live buffers
if that is larger."""
from harness.readers import hbm_peak_pct as read  # noqa: F401
