"""Shared code of the chip benchmark: the yardstick that later changes to
the serving stack are measured with.  Nothing here imports the program
under test except :mod:`bench.lib.harness`, which drives it."""
