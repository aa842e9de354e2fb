"""Launchers: the pipeline stage mesh, the compile cache, training / serving CLIs."""
