"""The yardstick: load generation, the request ledger and its metric
arithmetic, the trace reduction, the table of peaks and the ops/bytes
functions. Nothing here imports the program under test."""
