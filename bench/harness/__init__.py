"""The benchmark's own code: traffic, driving the served path, the work
count and peak table, trace reduction and the reference comparison."""
