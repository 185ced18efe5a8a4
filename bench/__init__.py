"""The chip benchmark of madupite (see ``BENCHMARK.json`` and ``PERF.md``)."""
