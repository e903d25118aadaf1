"""The benchmark's yardstick: traffic, trace reduction, peaks."""
