"""The chip benchmark: harness, traffic generator, reference and readers."""
