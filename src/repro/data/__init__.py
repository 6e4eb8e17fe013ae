"""Synthetic dataset generators matching the paper's datasets: planted-
partition graphs and DTI-like point clouds."""

from repro.data.sbm import sbm_graph  # noqa: F401
from repro.data.pointcloud import dti_like_pointcloud  # noqa: F401
