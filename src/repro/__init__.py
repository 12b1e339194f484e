"""repro — AutoAnalyzer-JAX: automatic performance debugging of SPMD
programs (Liu & Zhan et al., 2011) as a first-class feature of a multi-pod
JAX training/inference framework.  See README.md."""

__version__ = "0.1.0"
