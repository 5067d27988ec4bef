"""On-chip benchmark of the conversational serving tier (``run.py``)."""
