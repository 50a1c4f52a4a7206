"""Input generators of the benchmark's deployments, made from a seed on the
device. Each module here is named by a configuration file's
``generator`` key and exposes ``make(key, cfg)``."""
