"""Known-optimum LP generators, kept with the benchmark so that a change
to the program's own generators cannot move the yardstick."""
