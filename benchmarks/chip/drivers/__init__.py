"""Drivers: the general code behind traffic mixes.  A traffic file names
its driver (``"driver": "train"`` -> ``drivers/train.py``); every number
the driver needs comes from the traffic and configuration files."""
