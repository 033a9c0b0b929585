"""Plain references the benchmark compares the program with.

They import nothing of the program under test and take nothing it made:
weights, token tables and the anchor score matrix are made by the
benchmark (``bench/cell.py``) and handed to both sides.
"""
