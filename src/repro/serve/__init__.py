"""Serving: the DeepMapping batched lookup server (the paper's
deployment)."""

from repro.serve.engine import LookupServer  # noqa: F401
