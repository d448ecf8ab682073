"""The chip benchmark: one command runs one cell once (see README.md)."""
