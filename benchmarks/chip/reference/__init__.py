"""Plain reference for the scoring path, independent of ``repro``.

Straightforward numpy, written from the method's definitions: the Halton
machine population, Eq. 1 with the default target, the cost model's area
and power, Pareto extraction and the scalarized co-design objective with
its backtracking descent.  Nothing here imports the system under test or
takes anything it computed; the inputs are the workload profiles' raw
fields and the numbers in the configuration files.

Every function takes a ``dtype``.  The reference runs in float64; the
control of each cell runs the same code one precision lower (bfloat16 for
the float32 sweeps, float32 for the float64 co-design) and must come out
as not correct.
"""
