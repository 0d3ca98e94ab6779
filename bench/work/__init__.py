"""Work counts of the program's kernels, from the algorithm's own sizes
(real rows, |O| and border sizes), never from padded capacities or HLO.
One module per kernel: ``work(...) -> (flops, bytes)``."""
