"""Part of the chip benchmark (see ``run.py``)."""
