"""The benchmark's shared code: everything a cell needs that is not one
configuration, one traffic mix, one generator or one per-layer metric.

Later PRs may add files beside these and entries to ``BENCHMARK.json``; they
may not edit a file that is here.  From the program under test the harness
takes only the system (``distributed_tensorflow_tpu``) and what it reports
(``Engine.stats()``, losses, tokens); the yardstick is all in this directory.
"""
