"""The benchmark's own machinery: traffic, client, work functions, trace
reduction and the correctness comparison.  Nothing here is specific to
one configuration, traffic mix or per-layer metric."""
