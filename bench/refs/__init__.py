"""Plain references of the configurations' semantics."""
