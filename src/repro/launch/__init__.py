"""Launcher: device meshes, step builders, train/serve drivers."""
