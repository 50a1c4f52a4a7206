"""Traffic drivers: one general generator per kind of traffic, named by a
mix's ``driver`` key and steered by the mix's parameters alone."""
