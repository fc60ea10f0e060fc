"""On-chip benchmark: one command runs one cell (configuration x traffic)."""
