"""The benchmark's yardstick: the data generator and a plain LCM/LAMP oracle.

These are copies, in numpy, of what the program's own `repro.data.synthetic`,
`repro.core.lcm`, `repro.core.lamp` and `repro.stats.fisher` compute.  They
import nothing of the program, so a change to the program cannot move them.
"""
