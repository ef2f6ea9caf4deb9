"""Operation counts of model families, from shapes."""
