"""Traffic generation from a seed."""
