"""repro: counterfactual simulation for large-scale systems with burnout
variables (Heymann, CS.DC 2025) — scenario sweeps and an always-on what-if
service in JAX, for TPUs."""
__version__ = "1.0.0"
