"""Shared yardstick of the benchmark: spec loading, graphs, the traffic
loop, the plain reference, the schedule check, the trace reduction and the
table of published peaks."""
