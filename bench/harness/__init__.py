"""The benchmark's own library: paths and cache set-up, clocks, the peak
table, operation and byte counts, trace reduction and the cell runner.
Nothing here imports the program under test except ``cell`` and the jobs,
which drive it."""
