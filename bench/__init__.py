"""The serving benchmark: see harness.py for one run of a cell."""
