"""Plain float32 references, one module per model family, named by the
configuration file's ``reference`` key."""
