"""Traffic mixes: data files read by one generator, and the open-loop
driver."""
