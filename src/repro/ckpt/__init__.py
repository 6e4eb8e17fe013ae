"""Fault-tolerant checkpointing: atomic versioned saves and auto-resume."""

from repro.ckpt.manager import CheckpointManager  # noqa: F401
