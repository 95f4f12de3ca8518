"""Launcher: production meshes, train/serve drivers."""
