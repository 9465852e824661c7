"""Chip benchmark of the XCT reconstructor; ``python chipbench/run.py``."""
