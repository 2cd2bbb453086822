"""Shared pieces of the on-chip serving benchmark (see ``bench/run_cell.py``)."""
