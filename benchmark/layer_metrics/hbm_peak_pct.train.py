"""Device memory held on the fullest chip when the window closes, over
``bytes_limit``, training cells: the allocator's live buffers (the state)
plus what it has reserved for the step program's temporaries, or its peak of
live buffers if that is larger."""
from harness.readers import hbm_peak_pct as read  # noqa: F401
