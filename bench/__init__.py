"""The chip benchmark of the DeepMapping store (see ``BENCHMARK.json``)."""
