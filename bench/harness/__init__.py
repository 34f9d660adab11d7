"""The benchmark's general code: cell loading, device, daemon, traffic
modes, trace reduction and statistics.  Nothing here names a
configuration, a traffic mix or a metric: those are files found by name."""
