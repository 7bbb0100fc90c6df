"""The yardstick: cell resolution, traffic, statistics, trace reduction."""
