"""The benchmark's plain reference, kept apart from the program.

Straightforward numpy versions of the programs the cells run, written
from their definitions and importing nothing of the program under test.
Each takes the working precision as `dtype`: the configuration's own
precision gives the reference, and the next precision below it gives the
control that the comparison has to fail (see `bench.run.check`).
"""
