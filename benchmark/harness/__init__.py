"""The benchmark's shared code: everything a cell needs that is not one
configuration, one model family, one traffic mix, one generator or one
per-layer metric.  No file here knows a model: what depends on the model is
the family's file (``../families/<family>.py``, with its plain reference).

Later PRs may add files beside these and entries to ``BENCHMARK.json``; they
may not edit a file that is here.  From the program under test the harness
takes only the system (``distributed_tensorflow_tpu``) and what it reports
(``Engine.stats()``, losses, tokens); the yardstick is all under ``benchmark/``.
"""
