"""On-chip benchmark of masked sparse products: triangle counting cells."""
