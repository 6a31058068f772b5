from repro.ft import checkpoint  # noqa: F401
