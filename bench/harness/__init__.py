"""The benchmark's own code: cell lookup, data generation, the system under
test and its warm-up, trace reduction and the scan-work arithmetic."""
