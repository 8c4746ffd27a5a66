"""Data substrate: synthetic SOSD-style datasets and workload mixtures."""
from repro.data import datasets, workloads

__all__ = ["datasets", "workloads"]
