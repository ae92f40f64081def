"""On-chip benchmark of the served subgraph matcher (see ``run.py``)."""
