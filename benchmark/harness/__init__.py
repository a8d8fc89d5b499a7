"""The yardstick: manifest checks, batch generation, peaks, FLOP and byte
counts, the trace reduction and the result line.  Nothing here imports the
program; the drivers do."""
