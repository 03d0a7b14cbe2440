"""The yardstick's own arithmetic: peaks, statistics, traffic generation."""
