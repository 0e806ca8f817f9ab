"""The chip benchmark: one run of one cell of ``BENCHMARK.json`` on the
TPU it is started on. ``run.py`` is the entry point; README.md says how
to add a configuration, a traffic mix or a metric as files."""
