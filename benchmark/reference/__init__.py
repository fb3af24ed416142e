"""Plain float32 references, one module per published ``model_type``."""
