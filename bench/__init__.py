"""The sort's benchmark on TPU chips: one cell per process (``run.py``)."""
