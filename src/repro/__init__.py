"""repro — Compression Aware Physical Database Design (PVLDB 4(10), 2011)
reproduced faithfully (repro.core) and adapted into a multi-pod JAX
training/serving framework (repro.design + models/train/serve/launch).
See README.md."""

__version__ = "1.0.0"
