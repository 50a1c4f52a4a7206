"""Launch layer: sweep meshes, the compile cache, and the cost models the
plan tuner reads."""
from repro.launch.mesh import SweepMeshSpec

__all__ = ["SweepMeshSpec"]
