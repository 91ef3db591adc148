"""Distributed runtime: halo exchange and overlap (paper C6), int8 halo
compression, elasticity and fault handling."""
from . import halo, overlap, compression, fault, elastic

__all__ = ["halo", "overlap", "compression", "fault", "elastic"]
