"""Pallas TPU kernels. One lives here: ``distance``, the tiled
pairwise-distance seed rows behind the analyzer's ``pallas`` distance
backend (compiled on a TPU, interpret mode elsewhere). Import it as
``from repro.kernels import distance``.
"""
