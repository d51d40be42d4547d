"""Plain float32 references, one module per model family, found by the
`reference` key of a configuration file. Each module imports nothing of
the program and makes its weights from the seed (`bench/weights.py`)."""
