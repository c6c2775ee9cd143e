"""On-chip benchmark of the FleetEngine round path (see ``run.py``)."""
