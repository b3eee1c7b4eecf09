"""The chip benchmark: history in, verdict out, through the checker."""
