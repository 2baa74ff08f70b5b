"""Utilities: platform, paths, compile cache, structured metrics."""
