"""The benchmark's own yardstick: traffic, trace reduction, peaks, checks.

Nothing here imports the program under test except where a driver hands
it in; later changes to ``src/`` cannot move these numbers."""
