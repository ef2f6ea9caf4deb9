"""Traffic kinds: one module each, found by name."""
