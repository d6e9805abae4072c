"""Launchers: production mesh, train/serve entries."""

from .mesh import describe, make_local_mesh, make_production_mesh

__all__ = ["describe", "make_local_mesh", "make_production_mesh"]
