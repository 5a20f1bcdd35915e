"""repro — SSumM (KDD 2020) sparse graph summarization and summary queries in jax."""

__version__ = "1.0.0"
