"""Builders: one module per family of configuration. A configuration
file names its family; ``perfbench.run`` imports the module of that
name and calls ``build(config, device, variant)``."""
