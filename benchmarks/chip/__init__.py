"""The on-chip benchmark of the BB-ANS codec (see ``harness.py``)."""
