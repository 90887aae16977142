"""Plain float32 references, one module per family of configurations."""
