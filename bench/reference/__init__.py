"""The plain reference that decides ``correct``: model.py and compare.py."""
