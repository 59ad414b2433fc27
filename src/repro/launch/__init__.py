"""Launchers: production mesh, roofline analysis, train/serve."""
