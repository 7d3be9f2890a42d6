"""TPC-H for the chip benchmark: data, the six query builders, references.

Kept with the benchmark so that a change to the program under test cannot
change the data, the queries or the oracle that judges them.
"""
