"""Chip benchmark of the fused session path (``python bench/run.py``)."""
