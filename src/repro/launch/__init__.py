"""Launcher layer: the compile cache and the serving launcher."""
