"""What every cell shares: manifest lookup, device check and peaks, the
compile and window clocks, the trace reduction, the result line."""
