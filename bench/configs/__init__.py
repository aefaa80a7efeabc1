"""Deployments: ``<name>.json`` holds the configuration as run, ``<name>.py``
builds it from the seed and holds its plain reference."""
