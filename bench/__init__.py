"""The on-chip benchmark of the EF-HC fleet engine (see harness.py)."""
