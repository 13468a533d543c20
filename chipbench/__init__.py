"""On-chip benchmark of the tensor store: reads into HBM (see run.py)."""
