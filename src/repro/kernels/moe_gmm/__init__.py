"""Dropless grouped matmul over the experts a chip holds."""
